"""Fused All-Gather + GEMM Pallas TPU kernel — paper §4.1 Push model.

ONE kernel per device replaces the [all-gather kernel; GEMM kernel] BSP
pair, eliminating all three taxes:

* Kernel-Launch tax: a single ``pl.pallas_call`` contains both the
  communication schedule and the MXU compute.
* Bulk-Synchronous tax: a ring schedule — at ring step t the MXU
  multiplies the shard that arrived at step t-1 while the DMA engines
  push the shard onward to the right neighbour. Synchronization is
  per-shard DMA semaphores (TPU's hardware analogue of Iris's
  inbox+flag), not a global barrier.
* Inter-Kernel locality tax: arriving shards land directly in the VMEM
  inbox and are consumed from VMEM by the MXU; the gathered A never
  exists in HBM.

Layout is the paper's Figure 3: A:(M, K) sharded on K columns — each
device holds A_i:(M, K/W); B:(K, N) replicated; C = Σ_s A_s·B_s with
B's row-block s fetched HBM→VMEM per step (N-tiled).

The VMEM inbox ``a_bufs`` has one slot per source rank — exactly the
paper's ``Inbox_d(r)`` (Algorithm 2) — but filled by neighbour-to-
neighbour ring hops (ICI-native) instead of W-1 direct pushes.

Grid: (N/bn, W) — N tile major, ring step minor. The whole ring runs
during the first N tile; later tiles consume the now-complete inbox.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import jax_compat


def _ag_gemm_kernel(a_ref, b_ref, o_ref, a_bufs, b_buf, acc_ref,
                    local_sem, send_sem, recv_sem, fetch_sem,
                    *, axis: str, W: int, nn: int, bn: int):
    i = lax.axis_index(axis)
    n = pl.program_id(0)          # N tile (major)
    t = pl.program_id(1)          # ring step (minor)
    k = a_ref.shape[-1]
    s = lax.rem(i - t + W, W)     # shard id handled at this ring step

    @pl.when((n == 0) & (t == 0) & (W > 1))
    def _barrier():
        # Neighbourhood barrier: nobody pushes into our inbox before
        # we are inside the kernel (symmetric-heap readiness
        # handshake).
        barrier = pltpu.get_barrier_semaphore()
        right = lax.rem(i + 1, W)
        left = lax.rem(i - 1 + W, W)
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=(right,),
            device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_signal(
            barrier, inc=1, device_id=(left,),
            device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 2)

    @pl.when((n == 0) & (t == 0))
    def _load_own():
        local = pltpu.make_async_copy(a_ref, a_bufs.at[i], local_sem)
        local.start()
        local.wait()

    # ring hop: forward shard s to the right neighbour's inbox slot s
    copy = pltpu.make_async_remote_copy(
        src_ref=a_bufs.at[s],
        dst_ref=a_bufs.at[s],
        send_sem=send_sem, recv_sem=recv_sem,
        device_id=(lax.rem(i + 1, W),),
        device_id_type=pltpu.DeviceIdType.MESH,
    )

    @pl.when((n == 0) & (t > 0) & (W > 1))
    def _recv():
        copy.wait_recv()          # shard s arriving from the left

    @pl.when((n == 0) & (t < W - 1) & (W > 1))
    def _push():
        copy.start()

    # fetch B row-block s for this N tile (HBM -> VMEM)
    fetch = pltpu.make_async_copy(
        b_ref.at[pl.ds(s * k, k), pl.ds(n * bn, bn)], b_buf, fetch_sem)
    fetch.start()
    fetch.wait()

    @pl.when(t == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_bufs[s], b_buf[...],
                            preferred_element_type=jnp.float32)

    @pl.when((n == 0) & (t < W - 1) & (W > 1))
    def _sent():
        copy.wait_send()          # buffer free before the next hop reuses it

    @pl.when(t == W - 1)
    def _emit():
        o_ref[pl.ds(0, o_ref.shape[0]), pl.ds(n * bn, bn)] = (
            acc_ref[...].astype(o_ref.dtype))


def ag_gemm_fused(a_shard, b_full, *, axis: str, bn: int = 256,
                  interpret=None, collective_id: int = 7):
    """Per-device body (call under shard_map, manual over `axis`).

    a_shard: (M, K/W) local shard; b_full: (K, N) replicated.
    Returns (M, N) = concat_K(A) @ B on every device.
    """
    M, k = a_shard.shape
    K, N = b_full.shape
    if K % k != 0:
        raise ValueError(
            f"ag_gemm_fused: B rows K={K} must be a multiple of the "
            f"local A shard width k={k}")
    W = K // k
    # clamp bn to the largest divisor of N <= bn (the N grid must tile
    # exactly; a plain min() used to crash an assert for non-multiple N)
    bn = min(bn, N)
    while N % bn:
        bn -= 1
    if N >= 16 and bn < 16:
        # no usable divisor (e.g. prime N): a handful-of-lanes tile grid
        # is vector-misaligned and orders of magnitude slow on the MXU —
        # refuse loudly rather than silently degrade to bn=1
        raise ValueError(
            f"ag_gemm_fused: N={N} has no divisor >= 16 to tile the "
            f"output columns (largest <= bn is {bn}); pad N to a "
            f"128-multiple or use the XLA ring fallback")
    nn = N // bn
    if interpret is None:
        interpret = jax_compat.default_interpret()

    return pl.pallas_call(
        functools.partial(
            _ag_gemm_kernel, axis=axis, W=W, nn=nn, bn=bn),
        grid=(nn, W),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.ANY),   # a_shard (HBM)
            pl.BlockSpec(memory_space=pltpu.ANY),   # b_full  (HBM)
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((M, N), a_shard.dtype),
        scratch_shapes=[
            pltpu.VMEM((W, M, k), a_shard.dtype),   # per-source inbox
            pltpu.VMEM((k, bn), b_full.dtype),      # B row-block tile
            pltpu.VMEM((M, bn), jnp.float32),       # accumulator
            pltpu.SemaphoreType.DMA,                # local copy
            pltpu.SemaphoreType.DMA,                # send
            pltpu.SemaphoreType.DMA,                # recv
            pltpu.SemaphoreType.DMA,                # B fetch
        ],
        interpret=jax_compat.pallas_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id,
            dimension_semantics=("arbitrary", "arbitrary")),
    )(a_shard, b_full)
