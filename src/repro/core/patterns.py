"""Pattern registry: where the paper's technique plugs into the model.

Model code calls :func:`project_up` / :func:`project_down` /
:func:`decode_attn` instead of raw einsums. Dispatch on the ambient
``DistContext.fusion_mode``:

* ``bsp``   — explicit collective then dot inside shard_map (the paper's
              RCCL baseline, reproduced structurally).
* ``ring``  — overlapped ring collective-matmul (the paper's technique).
* ``auto``  — plain einsum + sharding constraints: XLA SPMD decides. This
              is the production default and the *fastest honest baseline*
              (XLA may itself overlap); ``bsp`` exists to reproduce the
              paper's explicit serialization.

When the model axis is trivial (single-device smoke tests) everything
degrades to a local einsum.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import collective_matmul as cm
from repro.core import flash_decode as fd
from repro.distributed import context as dctx
from repro.distributed.sharding_rules import constrain


def _mode(ctx) -> str:
    return ctx.fusion_mode


def _flat2(x):
    """Collapse leading dims to one M dim: (..., K) -> (M, K)."""
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def project_up(x, w, *, seq_axis_sharded: bool = True):
    """y[..., n] = x[..., k] @ w[k, n] with w column(TP)-sharded.

    ``x`` is sequence-sharded between blocks (SP); this is the paper's
    AG+GEMM site. Returns y column-sharded.
    """
    ctx = dctx.current()
    mode = _mode(ctx)
    W = ctx.model_axis_size
    if W == 1 or mode == "auto" or not seq_axis_sharded:
        y = jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
        # batch stays DP-sharded; output dim TP-sharded (None = replicated
        # in PartitionSpec, so every dim must be named explicitly!)
        return constrain(y, ctx.rules, "batch",
                         *(None,) * (y.ndim - 3), None, "act_mlp")
    if x.shape[-2] % W != 0:  # sequence not divisible: fall back
        return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
    m = "bsp" if mode == "bsp" else ("ring_bidir" if mode == "ring" else "ring")
    return cm.ag_gemm_m_sharded_sm(x, w.astype(x.dtype), ctx.mesh, mode=m)


def project_down(x, w):
    """y = x @ w with x column(TP)-sharded on K and w row-sharded:
    partial-sum GEMM + reduce-scatter back to sequence sharding."""
    ctx = dctx.current()
    mode = _mode(ctx)
    W = ctx.model_axis_size
    if W == 1 or mode == "auto":
        y = jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
        # re-establish sequence sharding between blocks (SP)
        return constrain(y, ctx.rules, "batch",
                         *(None,) * (y.ndim - 3), "seq", None)
    if x.shape[-2] % W != 0:
        return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
    m = "bsp" if mode == "bsp" else ("ring_bidir" if mode == "ring" else "ring")
    return cm.gemm_rs_sm(x, w.astype(x.dtype), ctx.mesh, mode=m)


def project_k_sharded(x, w):
    """The paper's Figure-3 AG+GEMM: x K-sharded, w replicated (decode
    row-parallel site)."""
    ctx = dctx.current()
    mode = _mode(ctx)
    W = ctx.model_axis_size
    if W == 1 or mode == "auto":
        return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
    m = "bsp" if mode == "bsp" else "ring_bidir"
    return cm.ag_gemm_k_sharded_sm(x, w.astype(x.dtype), ctx.mesh, mode=m)


def decode_attn(q, k_cache, v_cache, cur_len, *, scale,
                window: int | None = None):
    """Seq-sharded flash decode (paper §4.2) through the ambient context."""
    ctx = dctx.current()
    mode = _mode(ctx)
    W = ctx.model_axis_size
    if W == 1:
        return fd.reference_decode_attention(q, k_cache, v_cache, cur_len,
                                             scale, window)
    combine = {"bsp": "bsp", "ring": "ring", "auto": "rs_ag"}[mode]
    return fd.decode_attention_sm(q, k_cache, v_cache, cur_len, ctx.mesh,
                                  scale=scale, mode=combine, window=window)


def decode_attn_fused(q, k_new, v_new, k_cache, v_cache, cur_len, *, scale,
                      window: int | None = None,
                      rolling_len: int | None = None,
                      active=None):
    """Beyond-paper: cache-update + partial attention + combine in ONE
    shard_map region (see core.flash_decode.decode_attention_fused).
    ``active`` (B,) bool gates the per-slot cache write (continuous
    batching / chunked prefill). Returns (out, k_cache, v_cache). Used
    for fusion_mode ring; 'auto'/'bsp' keep the XLA-scatter
    baseline for comparison."""
    ctx = dctx.current()
    mode = _mode(ctx)
    combine = {"ring": "ring", "rs_ag": "rs_ag", "auto": "rs_ag",
               "bsp": "bsp"}[mode]
    return fd.decode_attention_fused_sm(
        q, k_new, v_new, k_cache, v_cache, cur_len, ctx.mesh, scale=scale,
        mode=combine, window=window, rolling_len=rolling_len, active=active)


def decode_attn_paged(q, k_new, v_new, k_pool, v_pool, cur_len,
                      block_tables, *, scale, window: int | None = None,
                      active=None, bounded: bool = True):
    """Paged flash decode: block-table-translated cache write + partial
    attention over the block-sharded pool + combine, in ONE shard_map
    region (all fusion modes share the region; they differ in the
    combine schedule — bsp keeps the paper's blocking all-gather).
    ``bounded`` (default) gathers each slot's referenced blocks through
    its table first, bounding per-slot work at table-width x block_size;
    ``bounded=False`` keeps the masked whole-pool-shard oracle.
    Returns (out, k_pool, v_pool)."""
    ctx = dctx.current()
    mode = _mode(ctx)
    combine = {"ring": "ring", "rs_ag": "rs_ag", "auto": "rs_ag",
               "bsp": "bsp"}[mode]
    return fd.decode_paged_attention_fused_sm(
        q, k_new, v_new, k_pool, v_pool, cur_len, block_tables, ctx.mesh,
        scale=scale, mode=combine, window=window, active=active,
        bounded=bounded)
