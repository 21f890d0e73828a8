"""Fused All-Gather+GEMM / GEMM+ReduceScatter — the paper's §4.1 on TPU.

The paper eliminates the BSP "Compute-Wait-Collective-Wait-Compute"
pattern by streaming tiles between producer and consumer. On TPU the
equivalent XLA-level construct is the **ring collective matmul**: a
`shard_map` region where each step multiplies the shard currently held
while `lax.ppermute` moves the next shard — the dot and the permute have
no data dependency, so XLA's latency-hiding scheduler overlaps them
(collective-permute-start / dot / collective-permute-done). The loop is
unrolled (world size is static) so the scheduler sees the full pipeline.

Three layouts, matching where the pattern appears in an LLM:

* ``ag_gemm_k_sharded``  — the paper's Figure-3 configuration: A:(M,K/W)
  sharded on K, B:(K,N) replicated; C = Σ_s A_s·B_s. Used for
  row-parallel (down/o) projections in decode.
* ``ag_gemm_m_sharded``  — A:(M/W,K) sequence-sharded rows, B:(K,N/W)
  column-parallel; gathers rows while computing. Used for up/qkv
  projections under sequence parallelism.
* ``gemm_rs``            — A:(M,K/W)·B:(K/W,N) partial sums ring-reduce-
  scattered over M. Used for down/o projections under SP.

Every function takes ``mode``:
  ``bsp``        faithful baseline (explicit collective, then dot)
  ``ring``       unidirectional ring (paper's Push model analogue)
  ``ring_bidir`` bidirectional ring (beyond-paper: uses both ICI
                 directions, halving per-step wire time)

All functions are *per-device* bodies — call them inside ``shard_map``
(helpers at the bottom wrap that), or through ``repro.core.patterns``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _ring_perms(axis: str, W: int):
    right = [(j, (j + 1) % W) for j in range(W)]
    left = [(j, (j - 1) % W) for j in range(W)]
    return right, left


# --------------------------------------------------------------------------
# Paper Figure 3: A sharded on K (columns), B replicated.
# --------------------------------------------------------------------------
def ag_gemm_k_sharded(a, b_full, *, axis: str, mode: str = "ring"):
    """C = concat_K(A) @ B with A K-sharded. Returns full (M, N) on every rank.

    a: (M, K/W) local shard, b_full: (K, N) replicated.
    """
    W = lax.axis_size(axis)
    i = lax.axis_index(axis)
    k = a.shape[-1]
    right, left = _ring_perms(axis, W)

    if mode == "bsp":
        # Compute-Wait-Collective-Wait-Compute: gather A fully, then one dot.
        a_full = lax.all_gather(a, axis, axis=a.ndim - 1, tiled=True)
        return jnp.einsum("...k,kn->...n", a_full, b_full)

    def b_block(s):
        return lax.dynamic_slice_in_dim(b_full, s * k, k, axis=0)

    if mode == "ring":
        cur = a
        acc = None
        for t in range(W):
            s = (i - t) % W  # global shard id currently held
            nxt = lax.ppermute(cur, axis, right) if t < W - 1 else None
            part = jnp.einsum("...k,kn->...n", cur, b_block(s))
            acc = part if acc is None else acc + part
            cur = nxt
        return acc

    if mode == "ring_bidir":
        h = k // 2
        cur_r, cur_l = a[..., :h], a[..., h:]
        acc = None
        for t in range(W):
            s_r, s_l = (i - t) % W, (i + t) % W
            nr = lax.ppermute(cur_r, axis, right) if t < W - 1 else None
            nl = lax.ppermute(cur_l, axis, left) if t < W - 1 else None
            br = lax.dynamic_slice_in_dim(b_full, s_r * k, h, axis=0)
            bl = lax.dynamic_slice_in_dim(b_full, s_l * k + h, h, axis=0)
            part = (jnp.einsum("...k,kn->...n", cur_r, br)
                    + jnp.einsum("...k,kn->...n", cur_l, bl))
            acc = part if acc is None else acc + part
            cur_r, cur_l = nr, nl
        return acc

    raise ValueError(f"unknown mode {mode!r}")


# --------------------------------------------------------------------------
# Sequence-parallel up-projection: A row(M)-sharded, B column-sharded.
# --------------------------------------------------------------------------
def ag_gemm_m_sharded(a, b, *, axis: str, mode: str = "ring"):
    """C = all_gather_M(A) @ B_local. a: (..., M/W, K), b: (K, N/W).

    Returns (..., M, N/W): full rows, column shard.
    """
    W = lax.axis_size(axis)
    i = lax.axis_index(axis)
    right, left = _ring_perms(axis, W)
    mdim = a.ndim - 2

    if mode == "bsp":
        a_full = lax.all_gather(a, axis, axis=mdim, tiled=True)
        return jnp.einsum("...mk,kn->...mn", a_full, b)

    m = a.shape[mdim]
    out_shape = a.shape[:mdim] + (m * W, b.shape[-1])

    if mode == "ring":
        cur = a
        out = jnp.zeros(out_shape, a.dtype)
        for t in range(W):
            s = (i - t) % W
            nxt = lax.ppermute(cur, axis, right) if t < W - 1 else None
            blk = jnp.einsum("...mk,kn->...mn", cur, b)
            out = lax.dynamic_update_slice_in_dim(out, blk, s * m, axis=mdim)
            cur = nxt
        return out

    if mode == "ring_bidir":
        h = m // 2
        cur_r = lax.slice_in_dim(a, 0, h, axis=mdim)
        cur_l = lax.slice_in_dim(a, h, m, axis=mdim)
        out = jnp.zeros(out_shape, a.dtype)
        for t in range(W):
            s_r, s_l = (i - t) % W, (i + t) % W
            nr = lax.ppermute(cur_r, axis, right) if t < W - 1 else None
            nl = lax.ppermute(cur_l, axis, left) if t < W - 1 else None
            blk_r = jnp.einsum("...mk,kn->...mn", cur_r, b)
            blk_l = jnp.einsum("...mk,kn->...mn", cur_l, b)
            out = lax.dynamic_update_slice_in_dim(out, blk_r, s_r * m, axis=mdim)
            out = lax.dynamic_update_slice_in_dim(out, blk_l, s_l * m + h,
                                                  axis=mdim)
            cur_r, cur_l = nr, nl
        return out

    raise ValueError(f"unknown mode {mode!r}")


# --------------------------------------------------------------------------
# Row-parallel down-projection with reduce-scatter over M.
# --------------------------------------------------------------------------
def gemm_rs(a, b, *, axis: str, mode: str = "ring"):
    """(Σ_ranks A_local @ B_local) reduce-scattered over M.

    a: (..., M, K/W), b: (K/W, N). Returns (..., M/W, N).
    """
    W = lax.axis_size(axis)
    i = lax.axis_index(axis)
    right, _ = _ring_perms(axis, W)
    mdim = a.ndim - 2
    M = a.shape[mdim]
    m = M // W

    if mode == "bsp":
        partial = jnp.einsum("...mk,kn->...mn", a, b)
        return lax.psum_scatter(partial, axis, scatter_dimension=mdim,
                                tiled=True)

    def a_block(s):
        return lax.dynamic_slice_in_dim(a, s * m, m, axis=mdim)

    if mode == "ring":
        acc = None
        for t in range(W):
            s = (i - t - 1) % W  # M-block whose accumulator is here now
            part = jnp.einsum("...mk,kn->...mn", a_block(s), b)
            acc = part if acc is None else lax.ppermute(acc, axis, right) + part
        return acc  # block i, fully reduced

    if mode == "ring_bidir":
        n = b.shape[-1]
        b_r, b_l = b[:, : n // 2], b[:, n // 2:]
        left = [(j, (j - 1) % W) for j in range(W)]
        acc_r = acc_l = None
        for t in range(W):
            s_r = (i - t - 1) % W
            s_l = (i + t + 1) % W
            pr = jnp.einsum("...mk,kn->...mn", a_block(s_r), b_r)
            pl = jnp.einsum("...mk,kn->...mn", a_block(s_l), b_l)
            acc_r = pr if acc_r is None else lax.ppermute(acc_r, axis, right) + pr
            acc_l = pl if acc_l is None else lax.ppermute(acc_l, axis, left) + pl
        return jnp.concatenate([acc_r, acc_l], axis=-1)

    raise ValueError(f"unknown mode {mode!r}")


# --------------------------------------------------------------------------
# Standalone ring all-gather (paper §4.2.3 "Independent All-Gather Kernel").
# --------------------------------------------------------------------------
def all_gather_ring(x, *, axis: str, gather_axis: int = 0):
    W = lax.axis_size(axis)
    i = lax.axis_index(axis)
    right, _ = _ring_perms(axis, W)
    m = x.shape[gather_axis]
    out_shape = list(x.shape)
    out_shape[gather_axis] = m * W
    out = jnp.zeros(tuple(out_shape), x.dtype)
    cur = x
    for t in range(W):
        s = (i - t) % W
        nxt = lax.ppermute(cur, axis, right) if t < W - 1 else None
        out = lax.dynamic_update_slice_in_dim(out, cur, s * m,
                                              axis=gather_axis)
        cur = nxt
    return out


# --------------------------------------------------------------------------
# shard_map wrappers (manual only over the TP axis; batch axes stay auto).
# --------------------------------------------------------------------------
def _smap(fn, mesh, in_specs, out_specs, axis: str, check_vma=True):
    # check_vma=True: required for jax to track varying-manual-axes so that
    # grads through the ring don't lower to an (unpartitionable)
    # PartitionId instruction under the SPMD partitioner. Wrappers whose
    # outputs are *semantically* replicated but computed from per-device
    # shard orders (k-sharded ring, decode combine) opt out — VMA analysis
    # cannot prove their replication.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={axis},
                         check_vma=check_vma)


def _check(cond: bool, msg: str):
    if not cond:
        raise ValueError(f"collective_matmul: {msg}")


def ag_gemm_k_sharded_sm(a, b, mesh, *, axis="model", mode="ring"):
    """a: (..., M, K) K globally sharded on `axis`; b: (K, N) replicated."""
    W = mesh.shape[axis]
    K = a.shape[-1]
    _check(K % W == 0,
           f"ag_gemm_k_sharded: K={K} must divide by the '{axis}' axis "
           f"size W={W} (A is K-sharded; a ragged shard would silently "
           f"drop columns)")
    _check(b.shape[0] == K,
           f"ag_gemm_k_sharded: A K dim {K} != B K dim {b.shape[0]}")
    _check(mode != "ring_bidir" or (K // W) % 2 == 0,
           f"ag_gemm_k_sharded: ring_bidir splits the local K shard "
           f"K/W={K // W} in half; it must be even — odd shards "
           f"mis-slice B's row blocks and return silently WRONG results "
           f"(measured max err ~5 on a unit test, not a rounding issue)")
    fn = functools.partial(ag_gemm_k_sharded, axis=axis, mode=mode)
    ins = (P(*(None,) * (a.ndim - 1), axis), P())
    return _smap(fn, mesh, ins, P(), axis, check_vma=False)(a, b)


def ag_gemm_m_sharded_sm(a, b, mesh, *, axis="model", mode="ring"):
    """a: (..., M, K) M sharded; b: (K, N) N sharded -> (..., M, N) N-sharded."""
    W = mesh.shape[axis]
    M, K = a.shape[-2], a.shape[-1]
    _check(M % W == 0,
           f"ag_gemm_m_sharded: M={M} must divide by the '{axis}' axis "
           f"size W={W} (A is M/row-sharded)")
    _check(b.shape[0] == K,
           f"ag_gemm_m_sharded: A K dim {K} != B K dim {b.shape[0]}")
    _check(b.shape[-1] % W == 0,
           f"ag_gemm_m_sharded: N={b.shape[-1]} must divide by W={W} "
           f"(B is N/column-sharded)")
    fn = functools.partial(ag_gemm_m_sharded, axis=axis, mode=mode)
    ins = (P(*(None,) * (a.ndim - 2), axis, None), P(None, axis))
    outs = P(*(None,) * (a.ndim - 1), axis)
    return _smap(fn, mesh, ins, outs, axis)(a, b)


def gemm_rs_sm(a, b, mesh, *, axis="model", mode="ring"):
    """a: (..., M, K) K sharded; b: (K, N) K sharded -> (..., M, N) M-sharded."""
    W = mesh.shape[axis]
    M, K = a.shape[-2], a.shape[-1]
    _check(M % W == 0,
           f"gemm_rs: M={M} must divide by the '{axis}' axis size W={W} "
           f"— the ring reduce-scatter hands out M/W-row blocks and a "
           f"ragged M would silently DROP the trailing {M % W} row(s)")
    _check(K % W == 0,
           f"gemm_rs: K={K} must divide by W={W} (A and B are K-sharded)")
    fn = functools.partial(gemm_rs, axis=axis, mode=mode)
    ins = (P(*(None,) * (a.ndim - 1), axis), P(axis, None))
    outs = P(*(None,) * (a.ndim - 2), axis, None)
    return _smap(fn, mesh, ins, outs, axis)(a, b)
