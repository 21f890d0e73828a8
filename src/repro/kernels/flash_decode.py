"""Fused distributed Flash Decode Pallas TPU kernel — paper Algorithm 4.

One kernel per device performs, without leaving the kernel:

  Part 1 (fused local attention + asynchronous push):
    * streams the local KV-cache shard HBM→VMEM in blocks, computing
      online-softmax partials (o, m, l) per head — GQA-native (one
      (g, D)×(D, blk) MXU matmul per KV head);
    * packs the partial into a single (B, H, D+2) tile and pushes it via
      remote DMA into every rank's inbox slot, signalling that rank's
      per-source DMA semaphore (the paper's RemoteAtomicInc flag).

  Part 2 (concurrent global reduction):
    * waits per-source (fine-grained, not a global barrier) and folds
      each arriving partial into the accumulator with the online-softmax
      combine; finalizes o/l into the output.

This is the paper's fully-"Fused Kernels" stage (§4.2.5): no separate
all-gather kernel (kernel-launch tax), no bulk barrier (bulk-sync tax),
partials never round-trip HBM between producer and consumer
(inter-kernel locality tax).

KV layout: strided sequence shard — local slot j holds global position
j·W + rank (see core.flash_decode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import jax_compat

NEG = float(jnp.finfo(jnp.float32).min)


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, out_ref,
               inbox, kbuf, vbuf, part, fetch_sem, send_sem, recv_sems,
               local_sem,
               *, axis: str, W: int, blk: int, scale: float):
    i = lax.axis_index(axis)
    B, H, D = q_ref.shape
    S_loc, KVH = k_ref.shape[1], k_ref.shape[2]
    g = H // KVH
    nblk = S_loc // blk
    cur_len = len_ref[0]

    @pl.when(W > 1)
    def _barrier():
        barrier = pltpu.get_barrier_semaphore()
        for d in range(W):
            if d != 0:
                pltpu.semaphore_signal(
                    barrier, inc=1,
                    device_id=(lax.rem(i + d, W),),
                    device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, W - 1)

    # ---------------- Part 1: local attention with online softmax ----------
    for b in range(B):
        for h in range(KVH):
            q_h = q_ref[b, pl.ds(h * g, g), :].astype(jnp.float32)  # (g, D)

            def body(j, carry):
                m, l, acc = carry
                fk = pltpu.make_async_copy(
                    k_ref.at[b, pl.ds(j * blk, blk), h, :], kbuf, fetch_sem)
                fk.start()
                fk.wait()
                fv = pltpu.make_async_copy(
                    v_ref.at[b, pl.ds(j * blk, blk), h, :], vbuf, fetch_sem)
                fv.start()
                fv.wait()
                gpos = (j * blk + lax.iota(jnp.int32, blk)) * W + i
                valid = gpos < cur_len
                s = (q_h @ kbuf[...].astype(jnp.float32).T) * scale
                s = jnp.where(valid[None, :], s, NEG)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                m_safe = jnp.where(m_new <= NEG / 2, 0.0, m_new)
                p = jnp.where(valid[None, :],
                              jnp.exp(s - m_safe[:, None]), 0.0)
                corr = jnp.where(m <= NEG / 2, 0.0,
                                 jnp.exp(m - m_safe))
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = (acc * corr[:, None]
                           + p @ vbuf[...].astype(jnp.float32))
                return m_new, l_new, acc_new

            m0 = jnp.full((g,), NEG, jnp.float32)
            l0 = jnp.zeros((g,), jnp.float32)
            a0 = jnp.zeros((g, D), jnp.float32)
            m, l, acc = lax.fori_loop(0, nblk, body, (m0, l0, a0))
            part[b, pl.ds(h * g, g), pl.ds(0, D)] = acc
            part[b, pl.ds(h * g, g), D] = m
            part[b, pl.ds(h * g, g), D + 1] = l

    # ---------------- asynchronous push to every rank's inbox --------------
    if W > 1:
        for d in range(W):
            dst = lax.rem(i + d, W)
            push = pltpu.make_async_remote_copy(
                src_ref=part, dst_ref=inbox.at[i],
                send_sem=send_sem, recv_sem=recv_sems.at[i],
                device_id=(dst,),
                device_id_type=pltpu.DeviceIdType.MESH)
            push.start()
            push.wait_send()
    else:
        cp = pltpu.make_async_copy(part, inbox.at[0], local_sem)
        cp.start()
        cp.wait()

    # ---------------- Part 2: concurrent global reduction ------------------
    for b in range(B):
        acc_o = jnp.zeros((H, D), jnp.float32)
        acc_m = jnp.full((H,), NEG, jnp.float32)
        acc_l = jnp.zeros((H,), jnp.float32)
        for src in range(W):
            if W > 1 and b == 0:
                # fine-grained wait: only for THIS source's arrival (the
                # canonical way to block on a DMA semaphore is a descriptor
                # with the expected byte count)
                pltpu.make_async_copy(inbox.at[src], inbox.at[src],
                                      recv_sems.at[src]).wait()
            o_s = inbox[src, b, :, pl.ds(0, D)]
            m_s = inbox[src, b, :, D]
            l_s = inbox[src, b, :, D + 1]
            m_new = jnp.maximum(acc_m, m_s)
            m_safe = jnp.where(m_new <= NEG / 2, 0.0, m_new)
            ca = jnp.where(acc_m <= NEG / 2, 0.0, jnp.exp(acc_m - m_safe))
            cb = jnp.where(m_s <= NEG / 2, 0.0, jnp.exp(m_s - m_safe))
            acc_o = acc_o * ca[:, None] + o_s * cb[:, None]
            acc_l = acc_l * ca + l_s * cb
            acc_m = m_new
        out_ref[b] = (acc_o / jnp.maximum(acc_l, 1e-30)[:, None]
                      ).astype(out_ref.dtype)


def _fd_paged_kernel(len_ref, tbl_ref, q_ref, k_ref, v_ref, out_ref,
                     inbox, kbuf, vbuf, part, fetch_sem, send_sem, recv_sems,
                     local_sem,
                     *, axis: str, W: int, scale: float):
    """Paged variant of ``_fd_kernel`` with BOUNDED per-slot work: the
    local KV shard is a slice of the BLOCK POOL — (n_loc, block_size,
    KVH, D), global block ``i*n_loc + j`` at local index j — and the
    scalar-prefetched per-slot block tables DRIVE the stream: for each
    slot the kernel walks the table slice (C entries), DMAs only the
    locally-owned referenced blocks into VMEM, and scores C*block_size
    positions — instead of iterating the whole pool dimension and
    searching the table per block (n_loc*block_size positions per slot,
    batch x the contiguous kernel's work at parity pool sizing). ``-1``
    reclaim holes and entries owned by other ranks issue a clamped
    padding fetch and are masked out of the online softmax. The
    partials and the remote-DMA push/combine halves are identical to
    the contiguous kernel.

    The caller may pass a leading ``[:, :gather_width]`` slice of the
    table (the serving layer's power-of-two gather-width bucket); the
    slice must cover every allocated entry of every slot."""
    i = lax.axis_index(axis)
    B, H, D = q_ref.shape
    n_loc, bs, KVH = k_ref.shape[0], k_ref.shape[1], k_ref.shape[2]
    C = tbl_ref.shape[1]
    g = H // KVH
    base = i * n_loc

    @pl.when(W > 1)
    def _barrier():
        barrier = pltpu.get_barrier_semaphore()
        for d in range(W):
            if d != 0:
                pltpu.semaphore_signal(
                    barrier, inc=1,
                    device_id=(lax.rem(i + d, W),),
                    device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, W - 1)

    # -------- Part 1: table-driven bounded local attention -----------------
    for b in range(B):
        cur_len = len_ref[b]
        for h in range(KVH):
            q_h = q_ref[b, pl.ds(h * g, g), :].astype(jnp.float32)  # (g, D)

            def body(c, carry):
                m, l, acc = carry
                # the table entry names the block; fetch it only if this
                # rank owns it (-1 holes and cross-shard blocks clamp to
                # a padding fetch of local block 0 and are masked below)
                gb = tbl_ref[b, c]
                owned = (gb >= base) & (gb < base + n_loc)
                j = jnp.where(owned, gb - base, 0)
                fk = pltpu.make_async_copy(
                    k_ref.at[j, :, h, :], kbuf, fetch_sem)
                fk.start()
                fk.wait()
                fv = pltpu.make_async_copy(
                    v_ref.at[j, :, h, :], vbuf, fetch_sem)
                fv.start()
                fv.wait()
                gpos = c * bs + lax.iota(jnp.int32, bs)
                valid = owned & (gpos < cur_len)
                s = (q_h @ kbuf[...].astype(jnp.float32).T) * scale
                s = jnp.where(valid[None, :], s, NEG)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                m_safe = jnp.where(m_new <= NEG / 2, 0.0, m_new)
                p = jnp.where(valid[None, :],
                              jnp.exp(s - m_safe[:, None]), 0.0)
                corr = jnp.where(m <= NEG / 2, 0.0,
                                 jnp.exp(m - m_safe))
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = (acc * corr[:, None]
                           + p @ vbuf[...].astype(jnp.float32))
                return m_new, l_new, acc_new

            m0 = jnp.full((g,), NEG, jnp.float32)
            l0 = jnp.zeros((g,), jnp.float32)
            a0 = jnp.zeros((g, D), jnp.float32)
            m, l, acc = lax.fori_loop(0, C, body, (m0, l0, a0))
            part[b, pl.ds(h * g, g), pl.ds(0, D)] = acc
            part[b, pl.ds(h * g, g), D] = m
            part[b, pl.ds(h * g, g), D + 1] = l

    # -------- asynchronous push to every rank's inbox ----------------------
    if W > 1:
        for d in range(W):
            dst = lax.rem(i + d, W)
            push = pltpu.make_async_remote_copy(
                src_ref=part, dst_ref=inbox.at[i],
                send_sem=send_sem, recv_sem=recv_sems.at[i],
                device_id=(dst,),
                device_id_type=pltpu.DeviceIdType.MESH)
            push.start()
            push.wait_send()
    else:
        cp = pltpu.make_async_copy(part, inbox.at[0], local_sem)
        cp.start()
        cp.wait()

    # -------- Part 2: concurrent global reduction --------------------------
    for b in range(B):
        acc_o = jnp.zeros((H, D), jnp.float32)
        acc_m = jnp.full((H,), NEG, jnp.float32)
        acc_l = jnp.zeros((H,), jnp.float32)
        for src in range(W):
            if W > 1 and b == 0:
                pltpu.make_async_copy(inbox.at[src], inbox.at[src],
                                      recv_sems.at[src]).wait()
            o_s = inbox[src, b, :, pl.ds(0, D)]
            m_s = inbox[src, b, :, D]
            l_s = inbox[src, b, :, D + 1]
            m_new = jnp.maximum(acc_m, m_s)
            m_safe = jnp.where(m_new <= NEG / 2, 0.0, m_new)
            ca = jnp.where(acc_m <= NEG / 2, 0.0, jnp.exp(acc_m - m_safe))
            cb = jnp.where(m_s <= NEG / 2, 0.0, jnp.exp(m_s - m_safe))
            acc_o = acc_o * ca[:, None] + o_s * cb[:, None]
            acc_l = acc_l * ca + l_s * cb
            acc_m = m_new
        out_ref[b] = (acc_o / jnp.maximum(acc_l, 1e-30)[:, None]
                      ).astype(out_ref.dtype)


def flash_decode_paged_fused(q, k_pool, v_pool, cur_len, tables, *,
                             axis: str, W: int, scale: float = 1.0,
                             interpret=None, collective_id: int = 10):
    """Per-device body (call under shard_map, manual over `axis`).

    q: (B, H, D) replicated; k_pool/v_pool: (n_loc, block_size, KVH, D)
    local slice of the paged block pool; cur_len: (B,) int32 per-slot
    lengths; tables: (B, C) int32 global block ids — C may be a
    gather-width leading slice of the full (B, max_blocks) table (see
    ``_fd_paged_kernel``); per-slot work is C * block_size positions.
    Returns (B, H, D).
    """
    B, H, D = q.shape
    if interpret is None:
        interpret = jax_compat.default_interpret()
    bs = k_pool.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),    # q
            pl.BlockSpec(memory_space=pltpu.ANY),     # k pool (HBM)
            pl.BlockSpec(memory_space=pltpu.ANY),     # v pool (HBM)
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((W, B, H, D + 2), jnp.float32),  # per-source inbox
            pltpu.VMEM((bs, D), k_pool.dtype),          # K block
            pltpu.VMEM((bs, D), v_pool.dtype),          # V block
            pltpu.VMEM((B, H, D + 2), jnp.float32),     # my partial
            pltpu.SemaphoreType.DMA,                    # kv fetch
            pltpu.SemaphoreType.DMA,                    # send
            pltpu.SemaphoreType.DMA((W,)),              # per-source recv
            pltpu.SemaphoreType.DMA,                    # local (W==1)
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _fd_paged_kernel, axis=axis, W=W, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=jax_compat.pallas_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
    )(cur_len, tables, q, k_pool, v_pool)


def flash_decode_fused(q, k_shard, v_shard, cur_len, *, axis: str, W: int,
                       blk: int = 128, scale: float = 1.0, interpret=None,
                       collective_id: int = 9):
    """Per-device body (call under shard_map, manual over `axis`).

    q: (B, H, D) replicated; k_shard/v_shard: (B, S_loc, KVH, D) strided
    local shard; cur_len: (1,) int32. Returns (B, H, D).
    """
    B, H, D = q.shape
    S_loc = k_shard.shape[1]
    blk = min(blk, S_loc)
    assert S_loc % blk == 0
    if interpret is None:
        interpret = jax_compat.default_interpret()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),    # q
            pl.BlockSpec(memory_space=pltpu.ANY),     # k (HBM)
            pl.BlockSpec(memory_space=pltpu.ANY),     # v (HBM)
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((W, B, H, D + 2), jnp.float32),  # per-source inbox
            pltpu.VMEM((blk, D), k_shard.dtype),        # K block
            pltpu.VMEM((blk, D), v_shard.dtype),        # V block
            pltpu.VMEM((B, H, D + 2), jnp.float32),     # my partial
            pltpu.SemaphoreType.DMA,                    # kv fetch
            pltpu.SemaphoreType.DMA,                    # send
            pltpu.SemaphoreType.DMA((W,)),              # per-source recv
            pltpu.SemaphoreType.DMA,                    # local (W==1)
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _fd_kernel, axis=axis, W=W, blk=blk, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=jax_compat.pallas_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
    )(cur_len, q, k_shard, v_shard)
