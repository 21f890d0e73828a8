"""GQA attention: dense + blockwise (flash-style) paths, train/prefill/decode.

Sharding strategy (see DESIGN.md §3/§4):

* train/prefill: activations sequence-sharded between blocks; qkv/o
  projections are the paper's AG+GEMM / GEMM+RS sites (dispatched through
  ``repro.core.patterns``); the attention einsum itself is head-sharded by
  XLA (KV heads are broadcast up to Q heads first — same bytes as Q; on
  real TPU the Pallas kernels keep GQA native).
* decode: KV cache sequence-sharded in a strided layout; attention goes
  through the paper's distributed Flash Decode (core.flash_decode).

Masks: causal, sliding-window (mixtral), prefix-LM (paligemma),
bidirectional (hubert).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import patterns
from repro.distributed import context as dctx
from repro.distributed.sharding_rules import constrain
from repro.models.module import Param
from repro.models.layers import apply_rope

NEG_INF = jnp.finfo(jnp.float32).min


def attn_spec(cfg):
    d, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return {
        "wq": Param((d, H * hd), init="scaled", axes=("embed", "heads")),
        "wk": Param((d, KVH * hd), init="scaled", axes=("embed", "kv_heads")),
        "wv": Param((d, KVH * hd), init="scaled", axes=("embed", "kv_heads")),
        "wo": Param((H * hd, d), init="scaled", axes=("heads", "embed")),
    }


def _mask_bias(q_pos, kv_pos, *, causal, window, prefix_len):
    """(..., q, kv) additive fp32 bias (0 or NEG_INF)."""
    ok = jnp.ones((q_pos.shape[-1], kv_pos.shape[-1]), bool)
    if causal:
        c = q_pos[:, None] >= kv_pos[None, :]
        if prefix_len is not None:
            c = c | (kv_pos[None, :] < prefix_len)
        ok = ok & c
    if window is not None:
        ok = ok & (kv_pos[None, :] > q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF)


def dense_attention(q, k, v, *, scale, causal=True, window=None,
                    prefix_len=None):
    """Oracle / small-sequence path. q,k,v: (B, S, H, D) (kv repeated)."""
    B, S, H, D = q.shape
    pos = jnp.arange(S)
    bias = _mask_bias(pos, pos, causal=causal, window=window,
                      prefix_len=prefix_len)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    p = jax.nn.softmax(s + bias, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def blockwise_attention(q, k, v, *, scale, causal=True, window=None,
                        prefix_len=None, chunk_q=512, chunk_kv=1024):
    """Flash-style blockwise attention in pure JAX (no S×S buffer).

    Scans q chunks; inner scan over kv chunks carries online-softmax
    state. Chunks that are fully masked are skipped with lax.cond so no
    FLOPs or HBM traffic occur for them at run time.
    """
    B, S, H, D = q.shape

    def _divisor_chunk(want: int) -> int:
        # largest divisor of S that is <= want (vlm prefixes make S odd-sized)
        c = min(want, S)
        while S % c:
            c -= 1
        return c

    cq = _divisor_chunk(chunk_q)
    ck = _divisor_chunk(chunk_kv)
    nq, nk = S // cq, S // ck

    qc = jnp.moveaxis(q.reshape(B, nq, cq, H, D), 1, 0)       # (nq,B,cq,H,D)
    kc = jnp.moveaxis(k.reshape(B, nk, ck, H, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, nk, ck, H, D), 1, 0)

    def kv_needed(qi, ki):
        q_lo, q_hi = qi * cq, qi * cq + cq - 1
        k_lo, k_hi = ki * ck, ki * ck + ck - 1
        need = jnp.array(True)
        if causal:
            c = k_lo <= q_hi
            if prefix_len is not None:
                c = c | (k_lo < prefix_len)
            need = need & c
        if window is not None:
            need = need & (k_hi > q_lo - window)
        return need

    def q_body(_, q_in):
        qi, qblk = q_in
        qf = qblk.astype(jnp.float32)
        q_pos = qi * cq + jnp.arange(cq)

        def kv_body(carry, kv_in):
            ki, kblk, vblk = kv_in
            acc, m, l = carry

            def compute(_):
                kv_pos = ki * ck + jnp.arange(ck)
                bias = _mask_bias(q_pos, kv_pos, causal=causal,
                                  window=window, prefix_len=prefix_len)
                s = jnp.einsum("bqhd,bkhd->bhqk", qf,
                               kblk.astype(jnp.float32)) * scale
                s = s + bias
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
                p = jnp.exp(s - m_safe[..., None])
                p = jnp.where(jnp.isfinite(s), p, 0.0)
                corr = jnp.where(jnp.isfinite(m),
                                 jnp.exp(m - m_safe), 0.0)
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = (acc * corr[..., None]
                           + jnp.einsum("bhqk,bkhd->bhqd", p,
                                        vblk.astype(jnp.float32)))
                return acc_new, m_new, l_new

            new = lax.cond(kv_needed(qi, ki), compute,
                           lambda _: (acc, m, l), None)
            return new, None

        acc0 = jnp.zeros((B, H, cq, D), jnp.float32)
        m0 = jnp.full((B, H, cq), NEG_INF)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        (acc, m, l), _ = lax.scan(
            kv_body, (acc0, m0, l0),
            (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,cq,H,D)

    _, chunks = lax.scan(q_body, None, (jnp.arange(nq), qc))
    return jnp.moveaxis(chunks, 0, 1).reshape(B, S, H, D)


def apply_attn(params, x, cfg, *, positions=None, dense_threshold=2048):
    """Train/prefill attention. x: (B, S, d_model) seq-sharded."""
    ctx = dctx.current()
    B, S, d = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    if positions is None:
        positions = jnp.arange(S)[None, :]

    q = patterns.project_up(x, params["wq"]).reshape(B, S, H, hd)
    k = patterns.project_up(x, params["wk"]).reshape(B, S, KVH, hd)
    v = patterns.project_up(x, params["wv"]).reshape(B, S, KVH, hd)
    if not cfg.is_attention_free and cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    # broadcast KV heads up to Q heads (GQA); sharded on heads by constraint
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    q = constrain(q, ctx.rules, "batch", None, "act_heads", None)
    k = constrain(k, ctx.rules, "batch", None, "act_heads", None)
    v = constrain(v, ctx.rules, "batch", None, "act_heads", None)

    scale = 1.0 / (hd ** 0.5)
    prefix = cfg.num_prefix_tokens if cfg.prefix_lm else None
    if S <= dense_threshold:
        o = dense_attention(q, k, v, scale=scale, causal=cfg.causal,
                            window=cfg.sliding_window, prefix_len=prefix)
    else:
        o = blockwise_attention(q, k, v, scale=scale, causal=cfg.causal,
                                window=cfg.sliding_window, prefix_len=prefix,
                                chunk_q=cfg.attn_chunk_q,
                                chunk_kv=cfg.attn_chunk_kv)
    o = o.reshape(B, S, H * hd)
    return patterns.project_down(o, params["wo"])


# --------------------------------------------------------------- decode step
def _finite(x):
    """x with its non-finite entries zeroed."""
    return jnp.where(jnp.isfinite(x), x, jnp.zeros_like(x))


def decode_attn_step(params, x, cache, cur_len, cfg, active=None,
                     block_tables=None, bounded: bool = True):
    """One-token decode. x: (B, 1, d); cache: dict(k, v) strided seq-sharded
    (B, S_max, KVH, hd), or — with ``block_tables`` — a paged pool
    (n_blocks, block_size, KVH, hd) shared across slots. Returns
    (out (B,1,d), new cache).

    ``cur_len`` may be a scalar (lockstep) or a (B,) per-slot length
    vector that already includes this step's token for active slots.
    ``active`` (B,) bool marks slots that consume a token this step:
    inactive slots keep their cache byte-identical (the K/V write is a
    read-modify-write predicated on ``active``) and their length — this
    is what lets continuous batching run slots at different positions
    and chunked prefill stop early for short prompts.

    ``block_tables`` (B, max_blocks) int32 (paged serving): logical
    position p of slot b lives at pool block ``block_tables[b, p//bs]``,
    offset ``p % bs``. The write and the attention read both translate
    through the table; slots grow block-at-a-time instead of owning a
    contiguous max_len stripe. The table may be a leading slice of the
    full row (the serving layer's gather-width bucketing) as long as it
    covers every allocated entry. Sliding windows are applied as a
    validity mask (no rolling reclaim — out-of-window blocks stay
    resident until the slot frees; block-level reclaim is a scheduler
    concern). ``bounded`` picks the distributed paged work model:
    table-gather (bounded per-slot FLOPs, default) vs the masked
    whole-pool-shard oracle."""
    ctx = dctx.current()
    B = x.shape[0]
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    W = ctx.model_axis_size

    q = jnp.einsum("bod,dn->bon", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bod,dn->bon", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bod,dn->bon", x, params["wv"].astype(x.dtype))
    q = q.reshape(B, 1, H, hd)
    k = k.reshape(B, 1, KVH, hd)
    v = v.reshape(B, 1, KVH, hd)
    cl = jnp.asarray(cur_len)
    pos = (cl - 1).reshape(-1, 1) if cl.ndim else \
        jnp.broadcast_to((cl - 1).reshape(1, 1), (B, 1))
    if cfg.rope_theta:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)

    if block_tables is not None:
        # ---------------- paged path: translate through the block table
        from repro.core import flash_decode as fd
        cl_b = cl if cl.ndim else jnp.broadcast_to(cl.reshape(1), (B,))
        act = (jnp.ones((B,), bool) if active is None
               else jnp.asarray(active))
        scale = 1.0 / (hd ** 0.5)
        view = fd.paged_view(W, B, block_tables.shape[1],
                             cache["k"].shape[0])
        if view == "sharded":
            o, ck, cv = patterns.decode_attn_paged(
                q[:, 0], k[:, 0], v[:, 0], cache["k"], cache["v"], cl_b,
                block_tables, scale=scale, window=cfg.sliding_window,
                active=act, bounded=bounded)
        else:
            with jax.named_scope("kv_write"):
                # every slot contracts the pool's rows, a masked row with
                # weight 0, and 0 x NaN is NaN: a slot whose state went
                # non-finite writes zeros, so only its own output fails
                ck = fd.paged_write(cache["k"], _finite(k[:, 0]),
                                    block_tables, cl_b, act)
                cv = fd.paged_write(cache["v"], _finite(v[:, 0]),
                                    block_tables, cl_b, act)
            o = fd.paged_decode_attention(
                q[:, 0], ck, cv, cl_b, block_tables, scale,
                window=cfg.sliding_window, whole_pool=view == "pool")
        o = o.reshape(B, 1, H * hd)
        out = patterns.project_k_sharded(o, params["wo"])
        return out, {"k": ck, "v": cv}

    S_max = cache["k"].shape[1]

    # strided cache layout: global position p -> array index
    # (p % W) * (S_max // W) + p // W  (shard-local slot p // W on rank p % W)
    # Rolling mode (sliding window with cache == window size): positions
    # wrap modulo the cache; the cache then always holds exactly the last
    # `window` tokens, and softmax permutation-invariance keeps it exact.
    rolling = (cfg.sliding_window is not None
               and S_max <= cfg.sliding_window)
    if W > 1 and ctx.fusion_mode in ("ring", "rs_ag"):
        # fused ownership-aware path: update+attend+combine in one
        # shard_map region (no XLA scatter collectives)
        o, ck, cv = patterns.decode_attn_fused(
            q[:, 0], k[:, 0], v[:, 0], cache["k"], cache["v"], cl,
            scale=1.0 / (hd ** 0.5),
            window=None if rolling else cfg.sliding_window,
            rolling_len=S_max if rolling else None,
            active=active)
        o = o.reshape(B, 1, H * hd)
        out = patterns.project_k_sharded(o, params["wo"])
        return out, {"k": ck, "v": cv}
    p = cl - 1
    if rolling:
        p = p % S_max
    idx = (p % W) * (S_max // W) + p // W
    if cl.ndim:  # per-slot positions (continuous batching)
        # Read-modify-write: inactive slots rewrite their current value
        # at a clamped index, so the cache stays untouched for them.
        act = (jnp.ones((B,), bool) if active is None
               else jnp.asarray(active))

        def upd_one(cb, nb, ib, ab):
            cur = lax.dynamic_slice(cb, (ib, 0, 0), nb.shape)
            return lax.dynamic_update_slice(
                cb, jnp.where(ab, nb, cur), (ib, 0, 0))
        upd = jax.vmap(upd_one)
        ck = upd(cache["k"], k.astype(cache["k"].dtype), idx, act)
        cv = upd(cache["v"], v.astype(cache["v"].dtype), idx, act)
    else:
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, idx, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, idx, 0, 0))

    scale = 1.0 / (hd ** 0.5)
    eff_len = jnp.minimum(cl, S_max) if rolling else cl
    window = None if rolling else cfg.sliding_window
    o = patterns.decode_attn(q[:, 0], ck, cv, eff_len, scale=scale,
                             window=window)
    o = o.reshape(B, 1, H * hd)
    out = patterns.project_k_sharded(o, params["wo"])
    return out, {"k": ck, "v": cv}


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """KV cache. For sliding-window archs the cache is bounded by the
    window (rolling layout) — this is what makes long_500k sub-quadratic
    in memory for mixtral."""
    KVH, hd = cfg.n_kv_heads, cfg.hd
    if cfg.sliding_window is not None:
        max_len = min(max_len, cfg.sliding_window)
    return {"k": jnp.zeros((batch, max_len, KVH, hd), dtype),
            "v": jnp.zeros((batch, max_len, KVH, hd), dtype)}


def init_paged_cache(cfg, n_blocks: int, block_size: int,
                     dtype=jnp.bfloat16):
    """Paged KV pool: blocks are shared across slots (no batch dim) and
    indexed through per-slot block tables. No sliding-window bounding
    here — the window is a validity mask in the paged decode path, and
    per-slot capacity is whatever the table covers."""
    KVH, hd = cfg.n_kv_heads, cfg.hd
    return {"k": jnp.zeros((n_blocks, block_size, KVH, hd), dtype),
            "v": jnp.zeros((n_blocks, block_size, KVH, hd), dtype)}
