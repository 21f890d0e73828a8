"""Distributed Flash Decode — the paper's §4.2 workload, on TPU.

The KV cache is sharded over the `model` mesh axis on the **sequence**
dimension in a strided layout (global position p lives on rank p mod W,
local slot p div W). Each rank computes partial attention + online
softmax statistics over its local KV shard; partials are then combined
across ranks. Because softmax is permutation-invariant, the strided
layout is exact and keeps incremental decode writes single-rank.

The evolution ladder matches the paper:

* ``bsp``        — all_gather the partials, then a separate combine step
                   ("Compute-Wait-Collective-Wait-Compute": pays all
                   three taxes).
* ``ring``       — fine-grained ring pass: each step combines the triple
                   currently held while the next one is in flight
                   (paper §4.2.4 "Fine-Grained Waits" / Algorithm 4's
                   structure, as ppermute dataflow).
* ``rs_ag``      — beyond-paper: the combine op is associative, so do a
                   ring reduce-scatter over heads followed by all-gather:
                   2·size wire bytes instead of W·size. Wins when W or
                   the partial size is large.
* ``pallas``     — in-kernel remote DMA version (repro.kernels.flash_decode)
                   = the paper's fully Fused Kernels stage.

A *partial* is the triple (o, m, l): o = Σ exp(s−m)·V (unnormalized),
m = running max, l = Σ exp(s−m).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------- local part
def local_partial_attention(q, k_shard, v_shard, valid, scale):
    """Partial attention over a local KV shard.

    q: (B, H, D); k_shard/v_shard: (B, S_loc, KVH, D); valid: (B, S_loc) bool.
    Returns (o, m, l): (B, H, D), (B, H), (B, H) in fp32.
    GQA: H = KVH * q_per_kv; head h uses kv head h // q_per_kv.
    """
    B, H, D = q.shape
    KVH = k_shard.shape[2]
    kf = k_shard.astype(jnp.float32)
    g = H // KVH
    qg = q.astype(jnp.float32).reshape(B, KVH, g, D)
    kT = kf.transpose(0, 2, 1, 3)                       # (B, KVH, S, D)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, kT) * scale
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.where(valid[:, None, None, :], scores, neg)
    m = jnp.max(scores, axis=-1)                        # (B, KVH, g)
    # All-invalid shard: keep m finite so exp() underflows to 0 cleanly.
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)                             # (B, KVH, g)
    vT = v_shard.astype(jnp.float32).transpose(0, 2, 1, 3)  # (B, KVH, S, D)
    o = jnp.einsum("bkgs,bksd->bkgd", p, vT)            # (B, KVH, g, D)
    m_out = jnp.where(jnp.isfinite(m), m, neg)
    return (o.reshape(B, H, D), m_out.reshape(B, H), l.reshape(B, H))


def combine2(pa, pb):
    """Online-softmax combine of two partials (associative, commutative)."""
    oa, ma, la = pa
    ob, mb, lb = pb
    m = jnp.maximum(ma, mb)
    # guard fully-empty partials (m = -inf)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    ca = jnp.where(jnp.isfinite(ma), jnp.exp(ma - m_safe), 0.0)
    cb = jnp.where(jnp.isfinite(mb), jnp.exp(mb - m_safe), 0.0)
    o = oa * ca[..., None] + ob * cb[..., None]
    l = la * ca + lb * cb
    return (o, m, l)


def finalize(partial):
    o, m, l = partial
    return o / jnp.maximum(l, 1e-30)[..., None]


# --------------------------------------------------------- combine strategies
def combine_bsp(partial, *, axis: str):
    """Paper baseline: blocking all-gather, then a separate combine pass."""
    W = lax.axis_size(axis)
    gathered = jax.tree.map(
        lambda x: lax.all_gather(x, axis, axis=0, tiled=False), partial)
    acc = jax.tree.map(lambda x: x[0], gathered)
    for s in range(1, W):
        acc = combine2(acc, jax.tree.map(lambda x: x[s], gathered))
    return acc


def combine_ring(partial, *, axis: str):
    """Fine-grained: combine each arriving partial while the next flies."""
    W = lax.axis_size(axis)
    right = [(j, (j + 1) % W) for j in range(W)]
    cur = partial
    acc = partial
    for t in range(1, W):
        cur = jax.tree.map(lambda x: lax.ppermute(x, axis, right), cur)
        acc = combine2(acc, cur)
    return acc


def combine_rs_ag(partial, *, axis: str):
    """Beyond-paper: reduce-scatter over heads with the combine op, then
    all-gather. O(2·size) wire traffic vs O(W·size) for the ring pass."""
    W = lax.axis_size(axis)
    H = partial[0].shape[1]
    if H % W != 0:
        return combine_ring(partial, axis=axis)
    right = [(j, (j + 1) % W) for j in range(W)]
    i = lax.axis_index(axis)
    h = H // W

    def hblk(p, s):
        return jax.tree.map(
            lambda x: lax.dynamic_slice_in_dim(x, s * h, h, axis=1), p)

    acc = None
    for t in range(W):
        s = (i - t - 1) % W
        blk = hblk(partial, s)
        if acc is None:
            acc = blk
        else:
            acc = combine2(jax.tree.map(
                lambda x: lax.ppermute(x, axis, right), acc), blk)
    # acc: combined block i; all-gather blocks back.
    return jax.tree.map(
        lambda x: lax.all_gather(x, axis, axis=1, tiled=True), acc)


# ------------------------------------------------------------ full decode op
def decode_attention(q, k_cache, v_cache, cur_len, *, axis: str,
                     scale: float, mode: str = "ring",
                     window: int | None = None):
    """One decode step of seq-sharded flash attention (per-device body).

    q: (B, H, D) replicated over `axis`;
    k_cache/v_cache: (B, S_loc, KVH, D) local shard, strided layout;
    cur_len: scalar int32 — tokens (including current) in the cache.
    Returns (B, H, D) attention output, replicated.
    """
    W = lax.axis_size(axis)
    i = lax.axis_index(axis)
    S_loc = k_cache.shape[1]
    gpos = jnp.arange(S_loc, dtype=jnp.int32) * W + i      # global positions
    cl = jnp.asarray(cur_len)
    cl = cl.reshape(-1, 1) if cl.ndim else cl              # (B,1) or scalar
    valid = gpos[None, :] < cl
    if window is not None:
        valid = valid & (gpos[None, :] >= cl - window)
    valid = jnp.broadcast_to(valid, (q.shape[0], S_loc))
    partial = local_partial_attention(q, k_cache, v_cache, valid, scale)
    if mode == "bsp":
        acc = combine_bsp(partial, axis=axis)
    elif mode == "ring":
        acc = combine_ring(partial, axis=axis)
    elif mode == "rs_ag":
        acc = combine_rs_ag(partial, axis=axis)
    else:
        raise ValueError(f"unknown decode combine mode {mode!r}")
    return finalize(acc).astype(q.dtype)


def decode_attention_sm(q, k_cache, v_cache, cur_len, mesh, *, axis="model",
                        scale: float, mode: str = "ring",
                        window: int | None = None):
    """shard_map wrapper. q: (B,H,D) replicated on axis; caches seq-sharded
    (B, S, KVH, D) with S sharded on `axis` (strided layout is the caller's
    contract); batch dims may be sharded on other (auto) axes."""
    fn = functools.partial(decode_attention, axis=axis, scale=scale,
                           mode=mode, window=window)
    ins = (P(), P(None, axis, None, None), P(None, axis, None, None), P())
    return jax.shard_map(fn, mesh=mesh, in_specs=ins, out_specs=P(),
                         axis_names={axis}, check_vma=False)(
        q, k_cache, v_cache, cur_len)


# --------------------------------------------- fused update+attend (beyond-paper)
def decode_attention_fused(q, k_new, v_new, k_cache, v_cache, cur_len, *,
                           axis: str, scale: float, mode: str = "ring",
                           window: int | None = None,
                           rolling_len: int | None = None,
                           active=None):
    """One shard_map region does cache-update + partial attention + combine.

    The strided layout makes position ownership local: rank (p mod W) owns
    position p, so the cache write is a predicated LOCAL dynamic-update —
    the XLA auto-sharded alternative lowers the scatter into collectives
    (measured: thousands of collective-permutes per step at 88 layers).
    This is the paper's philosophy applied to the cache itself: replace a
    global data movement with fine-grained, ownership-aware dataflow.

    q: (B, H, D) replicated; k_new/v_new: (B, KVH, D); k_cache/v_cache:
    (B, S_loc, KVH, D) local shard. ``active`` (B,) bool (per-slot
    ``cur_len`` only): slots not consuming a token this step skip the
    cache write — their ``cur_len`` entry is the unchanged old length,
    so the ownership predicate must not fire for them.
    Returns (out, k_cache, v_cache).
    """
    W = lax.axis_size(axis)
    i = lax.axis_index(axis)
    S_loc = k_cache.shape[1]
    cl = jnp.asarray(cur_len)
    p = (cl - 1) % rolling_len if rolling_len is not None else cl - 1
    own = (p % W) == i
    if active is not None:
        own = own & jnp.asarray(active)
    slot = jnp.minimum(jnp.maximum(p, 0) // W, S_loc - 1)

    def upd(cache, new):
        if cl.ndim:      # per-slot positions
            def one(cb, nb, sb, ob):
                cur = lax.dynamic_slice_in_dim(cb, sb, 1, axis=0)
                val = jnp.where(ob, nb[None], cur)
                return lax.dynamic_update_slice_in_dim(cb, val, sb, axis=0)
            return jax.vmap(one)(cache, new.astype(cache.dtype), slot, own)
        cur = lax.dynamic_slice_in_dim(cache, slot, 1, axis=1)
        val = jnp.where(own, new[:, None].astype(cache.dtype), cur)
        return lax.dynamic_update_slice_in_dim(cache, val, slot, axis=1)

    k_cache = upd(k_cache, k_new)
    v_cache = upd(v_cache, v_new)

    eff_len = jnp.minimum(cl, rolling_len) if rolling_len is not None else cl
    out = decode_attention(q, k_cache, v_cache, eff_len, axis=axis,
                           scale=scale, mode=mode,
                           window=None if rolling_len is not None else window)
    return out, k_cache, v_cache


def decode_attention_fused_sm(q, k_new, v_new, k_cache, v_cache, cur_len,
                              mesh, *, axis="model", scale: float,
                              mode: str = "ring", window: int | None = None,
                              rolling_len: int | None = None,
                              active=None):
    cache_spec = P(None, axis, None, None)

    def fn(q, k_new, v_new, k_cache, v_cache, cur_len, *act):
        return decode_attention_fused(
            q, k_new, v_new, k_cache, v_cache, cur_len, axis=axis,
            scale=scale, mode=mode, window=window,
            rolling_len=rolling_len, active=act[0] if act else None)

    args = [q, k_new, v_new, k_cache, v_cache, cur_len]
    ins = [P(), P(), P(), cache_spec, cache_spec, P()]
    if active is not None:           # replicated (B,) active mask
        args.append(active)
        ins.append(P())
    outs = (P(), cache_spec, cache_spec)
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(ins),
                         out_specs=outs, axis_names={axis},
                         check_vma=False)(*args)


# --------------------------------------------------- paged (block-granular) KV
#
# The serving layer stores KV in a shared pool of fixed-size blocks,
# (n_blocks, block_size, KVH, D), indexed through a per-slot block table
# (B, max_blocks) of global block ids (-1 = unallocated). Logical
# position p of slot b lives at pool block table[b, p // bs], offset
# p % bs. Across the model axis the pool is sharded on the BLOCK dim in
# contiguous chunks: global block t lives on rank t // n_loc at local
# index t % n_loc. Softmax permutation-invariance makes any block->rank
# assignment exact, and keeps every block write single-rank — the same
# ownership-aware dataflow argument as the strided contiguous layout.

def gather_paged_view(pool, tables):
    """Materialize the logical per-slot view of a paged pool.

    pool: (n_blocks, bs, KVH, D); tables: (B, C) int32 global block ids.
    Returns (B, C*bs, KVH, D) in logical position order. Unallocated
    chunks (-1) gather a clamped garbage block — callers mask by cur_len,
    which never reaches into an unallocated chunk.
    """
    t = jnp.clip(tables, 0, pool.shape[0] - 1)
    v = pool[t]                                  # (B, C, bs, KVH, D)
    B, C, bs = v.shape[:3]
    return v.reshape(B, C * bs, *pool.shape[2:])


def paged_block_positions(tables, n_loc, rank, bs):
    """Logical positions held by this rank's pool shard, per slot.

    tables: (B, C); the local shard holds global blocks
    [rank*n_loc, (rank+1)*n_loc). Returns (gpos (B, n_loc, bs) int32
    logical positions, has (B, n_loc) bool — whether the local block is
    referenced by the slot's table at all). Each global block appears at
    most once per table row, so a masked max recovers its chunk index.
    """
    B, C = tables.shape
    gb = rank * n_loc + jnp.arange(n_loc, dtype=jnp.int32)
    match = tables[:, :, None] == gb[None, None, :]        # (B, C, n_loc)
    has = jnp.any(match, axis=1)
    chunk = jnp.max(jnp.where(match, jnp.arange(C, dtype=jnp.int32)
                              [None, :, None], 0), axis=1)  # (B, n_loc)
    gpos = chunk[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32)
    return gpos, has


def paged_local_partial_attention(q, k_loc, v_loc, valid, scale):
    """Partial attention over a local *pool* shard (no batch dim on KV:
    blocks are shared across slots; ``valid`` carries each slot's view).
    Delegates to :func:`local_partial_attention` with the shard broadcast
    over the batch — a view, not a copy; the einsum folds it.

    q: (B, H, D); k_loc/v_loc: (S_loc, KVH, D); valid: (B, S_loc) bool.
    """
    B = q.shape[0]
    kb = jnp.broadcast_to(k_loc[None], (B,) + k_loc.shape)
    vb = jnp.broadcast_to(v_loc[None], (B,) + v_loc.shape)
    return local_partial_attention(q, kb, vb, valid, scale)


def gather_owned_blocks(pool, tables, base):
    """Gather each slot's referenced blocks that live on THIS rank's pool
    shard — the bounded-work half of the table-gather paged decode.

    pool: (n_loc, bs, KVH, D) local shard holding global blocks
    [base, base + n_loc); tables: (B, C) int32 global block ids.
    Returns (view (B, C*bs, KVH, D) in logical position order,
    owned (B, C) bool). A table entry that is ``-1`` (a sliding-window
    reclaim hole) or lives on another rank gathers block 0 as padding and
    comes back with ``owned=False`` — callers mask those positions, so
    cross-shard misses and holes are never scored as real KV.

    Per-slot work is C*bs positions — bounded by the table width the
    caller hands in (``max_blocks``, or the live gather-width bucket) —
    instead of the whole n_loc*bs pool shard the masked-pool path scores.
    """
    n_loc = pool.shape[0]
    owned = (tables >= base) & (tables < base + n_loc)
    idx = jnp.where(owned, tables - base, 0)
    v = pool[idx]                                # (B, C, bs, KVH, D)
    B, C, bs = v.shape[:3]
    return v.reshape(B, C * bs, *pool.shape[2:]), owned


def paged_write(pool, new, tables, cur_len, active, *, owner_base=None,
                n_owned=None):
    """Write each active slot's new KV at its current position through the
    block table. pool: (n_loc, bs, KVH, D); new: (B, KVH, D). With
    owner_base/n_owned set, only blocks [owner_base, owner_base+n_owned)
    are local — writes outside the owned range (or to slots that are
    inactive / unallocated) are routed out of bounds and dropped.
    """
    bs = pool.shape[1]
    cl = jnp.asarray(cur_len)
    pos = jnp.maximum(cl - 1, 0)
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    ok = active & (blk >= 0)
    if owner_base is not None:
        ok = ok & (blk >= owner_base) & (blk < owner_base + n_owned)
        blk = blk - owner_base
    idx = jnp.where(ok, blk, pool.shape[0])          # OOB index -> dropped
    return pool.at[idx, off].set(new.astype(pool.dtype), mode="drop")


def decode_paged_attention_fused(q, k_new, v_new, k_pool, v_pool, cur_len,
                                 tables, *, axis: str, scale: float,
                                 mode: str = "ring",
                                 window: int | None = None, active=None,
                                 bounded: bool = True):
    """Paged analogue of :func:`decode_attention_fused` (per-device body).

    One shard_map region does block-table-translated cache write +
    partial attention over the local block shard + cross-rank combine.
    q: (B, H, D) replicated; k_new/v_new: (B, KVH, D); k_pool/v_pool:
    (n_loc, bs, KVH, D) local block shard; tables: (B, C) replicated;
    cur_len: (B,) per-slot lengths INCLUDING this step's token for
    active slots. Returns (out, k_pool, v_pool).

    ``bounded`` selects the per-slot work model:

    * ``True`` (default) — **bounded table-gather**: each rank gathers
      only the table rows it owns (:func:`gather_owned_blocks`) and
      scores C*bs positions per slot, where C is the table width the
      caller passes in. Callers shrink C to the live
      ``max_blocks_in_use`` watermark in padded power-of-two buckets
      (see ``serving.kv_cache.CachePool.gather_width``), so per-slot
      work is bounded at ``max_blocks * block_size`` and usually far
      less. ``-1`` reclaim holes and cross-shard entries are masked.
    * ``False`` — the masked-pool oracle: every slot is scored against
      the entire n_loc*bs local pool shard with a per-slot validity
      mask. Kept as the token-identity reference; at parity pool sizing
      it costs batch x the contiguous path's per-slot FLOPs.

    Both paths share the write, the combine schedules, and the online-
    softmax partial algebra, so they agree to float rounding and decode
    token-identical streams.
    """
    W = lax.axis_size(axis)
    i = lax.axis_index(axis)
    n_loc, bs = k_pool.shape[0], k_pool.shape[1]
    B = q.shape[0]
    cl = jnp.asarray(cur_len)
    act = (jnp.ones((B,), bool) if active is None
           else jnp.asarray(active))
    base = i * n_loc
    k_pool = paged_write(k_pool, k_new, tables, cl, act,
                         owner_base=base, n_owned=n_loc)
    v_pool = paged_write(v_pool, v_new, tables, cl, act,
                         owner_base=base, n_owned=n_loc)

    if bounded:
        # gather AFTER the write so this step's token is attended
        kview, owned = gather_owned_blocks(k_pool, tables, base)
        vview, _ = gather_owned_blocks(v_pool, tables, base)
        C = tables.shape[1]
        gpos = (jnp.arange(C, dtype=jnp.int32)[:, None] * bs
                + jnp.arange(bs, dtype=jnp.int32)[None, :])   # (C, bs)
        valid = owned[:, :, None] & (gpos[None] < cl[:, None, None])
        if window is not None:
            valid = valid & (gpos[None] >= cl[:, None, None] - window)
        partial = local_partial_attention(
            q, kview, vview, valid.reshape(B, C * bs), scale)
    else:
        gpos, has = paged_block_positions(tables, n_loc, i, bs)
        valid = has[:, :, None] & (gpos < cl[:, None, None])
        if window is not None:
            valid = valid & (gpos >= cl[:, None, None] - window)
        valid = valid.reshape(B, n_loc * bs)
        partial = paged_local_partial_attention(
            q, k_pool.reshape(n_loc * bs, *k_pool.shape[2:]),
            v_pool.reshape(n_loc * bs, *v_pool.shape[2:]), valid, scale)
    if W == 1:
        acc = partial
    elif mode == "bsp":
        acc = combine_bsp(partial, axis=axis)
    elif mode == "ring":
        acc = combine_ring(partial, axis=axis)
    elif mode == "rs_ag":
        acc = combine_rs_ag(partial, axis=axis)
    else:
        raise ValueError(f"unknown decode combine mode {mode!r}")
    return finalize(acc).astype(q.dtype), k_pool, v_pool


def decode_paged_attention_fused_sm(q, k_new, v_new, k_pool, v_pool, cur_len,
                                    tables, mesh, *, axis="model",
                                    scale: float, mode: str = "ring",
                                    window: int | None = None, active=None,
                                    bounded: bool = True):
    """shard_map wrapper: pool sharded on the block dim (contiguous
    chunks), everything else replicated. n_blocks must divide by the
    axis size (the serving pool rounds up at construction).

    Gather-width contract (``bounded=True``): the ``tables`` the caller
    passes may be a LEADING SLICE ``[:, :gather_width]`` of the full
    (B, max_blocks) table — per-slot work is then gather_width x
    block_size. The slice must cover every allocated (>= 0) entry of
    every active slot; serving callers bucket the width to the next
    power of two of the live ``max_blocks_in_use`` watermark so jit
    recompiles stay bounded at log2(max_blocks) (see
    ``lm.decode_step``)."""
    pool_spec = P(axis, None, None, None)

    def fn(q, k_new, v_new, kp, vp, cl, tb, *act):
        return decode_paged_attention_fused(
            q, k_new, v_new, kp, vp, cl, tb, axis=axis, scale=scale,
            mode=mode, window=window, active=act[0] if act else None,
            bounded=bounded)

    args = [q, k_new, v_new, k_pool, v_pool, cur_len, tables]
    ins = [P(), P(), P(), pool_spec, pool_spec, P(), P()]
    if active is not None:
        args.append(active)
        ins.append(P())
    outs = (P(), pool_spec, pool_spec)
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(ins),
                         out_specs=outs, axis_names={axis},
                         check_vma=False)(*args)


def paged_view(model_axis_size: int, batch: int, width: int,
               n_blocks: int) -> str:
    """Which KV a paged decode step attends over, from static shapes.

    ``"sharded"`` on a model axis wider than one
    (``patterns.decode_attn_paged``). On one device, ``"pool"`` when the
    pool holds no more blocks than the tables' slice names (``batch``
    slots x ``width`` columns): every slot reads the whole pool once, in
    place. Otherwise ``"gather"``: each slot gathers its table's blocks.
    The attention and the engine's ``attn_pool_view_dispatches`` counter
    both ask this function, so they cannot disagree.
    """
    if model_axis_size > 1:
        return "sharded"
    return "pool" if n_blocks <= batch * width else "gather"


def paged_decode_attention(q, k_pool, v_pool, cur_len, tables, scale,
                           window: int | None = None, *,
                           whole_pool: bool):
    """Served single-device paged decode attention.

    q: (B, H, D); k_pool/v_pool: (n_blocks, bs, KVH, D); cur_len: (B,)
    lengths including this step's token; tables: (B, C) global block ids
    (-1 = unallocated or reclaimed). Returns (B, H, D) in q's dtype.

    ``whole_pool`` (:func:`paged_view`'s ``"pool"``) attends every slot
    over the whole pool, shared by the batch and read in its stored
    dtype: a slot sees the blocks its table holds, so a block two tables
    share is valid for both. The scores are exact bf16 products
    accumulated in float32, the softmax is float32, and PV runs at HIGH
    precision, which splits the float32 softmax into two bf16 terms
    against V, exact in bf16. The result matches
    :func:`reference_paged_decode_attention` to float32 rounding, and a
    slot with no valid position (cur_len 0) returns zeros. A masked row
    still enters PV with weight 0, and 0 x NaN is NaN, so the pool must
    hold only finite values: the single-device write in
    ``attention.decode_attn_step`` zeroes non-finite K/V rows.

    Otherwise each slot attends over its own table's blocks through the
    oracle. A per-slot contraction at one query head per KV head is a
    batched matvec, which XLA on TPU lowers to a float32 multiply-reduce
    behind its own convert of the gathered view, bf16 or not, so the
    oracle costs the same.
    """
    if not whole_pool:
        return reference_paged_decode_attention(q, k_pool, v_pool, cur_len,
                                                tables, scale, window)
    B, H, D = q.shape
    n_blocks, bs, KVH, _ = k_pool.shape
    gpos, valid = paged_block_positions(tables, n_blocks, 0, bs)
    scores = jnp.einsum("bkgd,skd->bkgs", q.reshape(B, KVH, H // KVH, D),
                        k_pool.reshape(-1, KVH, D),
                        preferred_element_type=jnp.float32)
    cl = jnp.asarray(cur_len)[:, None, None]
    valid = valid[:, :, None] & (gpos < cl)
    if window is not None:
        valid = valid & (gpos >= cl - window)
    valid = valid.reshape(B, 1, 1, -1)
    scores = jnp.where(valid, scores * scale, -jnp.inf)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - jnp.where(jnp.isfinite(m), m, 0.0))
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,skd->bkgd", p, v_pool.reshape(-1, KVH, D),
                   precision=lax.Precision.HIGH,
                   preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.reshape(B, H, D).astype(q.dtype)


def reference_paged_decode_attention(q, k_pool, v_pool, cur_len, tables,
                                     scale, window: int | None = None):
    """Single-device paged oracle: gather the logical view, then dense
    attention. Bit-identical to the contiguous reference for equal
    logical capacity — gathered garbage beyond cur_len is masked with
    exactly the same NEG_INF scores."""
    kview = gather_paged_view(k_pool, tables)
    vview = gather_paged_view(v_pool, tables)
    return reference_decode_attention(q, kview, vview, cur_len, scale,
                                      window=window)


# ------------------------------------------------------- reference (1 device)
def reference_decode_attention(q, k, v, cur_len, scale,
                               window: int | None = None):
    """Oracle: dense softmax attention over the first cur_len positions."""
    B, H, D = q.shape
    S = k.shape[1]
    KVH = k.shape[2]
    g = H // KVH
    pos = jnp.arange(S)
    cl = jnp.asarray(cur_len)
    cl = cl.reshape(-1, 1) if cl.ndim else cl
    valid = pos[None, :] < cl
    if window is not None:
        valid = valid & (pos[None, :] >= cl - window)
    valid = jnp.broadcast_to(valid, (B, S))
    qg = q.astype(jnp.float32).reshape(B, KVH, g, D)
    kT = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, kT) * scale
    scores = jnp.where(valid[:, None, None, :], scores,
                       jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(scores, axis=-1)
    vT = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    o = jnp.einsum("bkgs,bksd->bkgd", p, vT)
    return o.reshape(B, H, D).astype(q.dtype)
