"""jit'd public wrappers for the Pallas kernels.

These are what the rest of the framework calls: shape checks, shard_map
plumbing, and VMEM-budget dispatch (shapes too large for the fused
kernel's VMEM working set fall back to the XLA ring implementation in
``repro.core`` — same schedule, compiler-generated).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import collective_matmul as cm
from repro.core import taxes
from repro.kernels import ag_gemm as _ag
from repro.kernels import flash_decode as _fd
from repro.kernels import matmul as _mm


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn"))
def matmul(a, b, bm: int = 256, bk: int = 512, bn: int = 256):
    return _mm.matmul(a, b, bm=bm, bk=bk, bn=bn)


def _vmem_ok(*arrays, budget: int = taxes.V5E.vmem_bytes) -> bool:
    import math
    tot = sum(jnp.dtype(x.dtype).itemsize * math.prod(x.shape)
              for x in arrays)
    return tot <= budget // 2     # leave half for double buffers / acc


def ag_gemm(a, b, mesh, *, axis: str = "model", bn: int = 256,
            use_pallas: bool = True):
    """Distributed AG+GEMM. a: (M, K) with K sharded over `axis` globally;
    b: (K, N) replicated. Returns (M, N) replicated."""
    W = mesh.shape[axis]
    M, K = a.shape
    if (not use_pallas or W == 1
            or not _vmem_ok(a, jax.ShapeDtypeStruct((K // W, bn), b.dtype))):
        return cm.ag_gemm_k_sharded_sm(a, b, mesh, axis=axis,
                                       mode="ring_bidir" if W > 1 else "bsp")

    fn = functools.partial(_ag.ag_gemm_fused, axis=axis, bn=bn)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(P(None, axis), P()),
                         out_specs=P(), axis_names={axis},
                         check_vma=False)(a, b)


def flash_decode(q, k_cache, v_cache, cur_len, mesh, *, axis: str = "model",
                 scale: float = 1.0, blk: int = 128):
    """Distributed flash decode, fused kernel. q: (B,H,D) replicated;
    caches (B, S, KVH, D) with S sharded on `axis` (strided layout)."""
    W = mesh.shape[axis]
    cl = jnp.asarray(cur_len, jnp.int32).reshape(1)
    fn = functools.partial(_fd.flash_decode_fused, axis=axis, W=W, blk=blk,
                           scale=scale)
    ins = (P(), P(None, axis, None, None), P(None, axis, None, None), P())
    return jax.shard_map(fn, mesh=mesh, in_specs=ins, out_specs=P(),
                         axis_names={axis}, check_vma=False)(
        q, k_cache, v_cache, cl)


def flash_decode_paged(q, k_pool, v_pool, cur_len, tables, mesh, *,
                       axis: str = "model", scale: float = 1.0):
    """Distributed paged flash decode, fused kernel. q: (B,H,D)
    replicated; k_pool/v_pool: (n_blocks, block_size, KVH, D) with the
    block dim sharded on `axis` (contiguous chunks — the serving pool's
    layout contract); cur_len: (B,) per-slot lengths; tables:
    (B, max_blocks) int32 block tables, replicated — or a leading
    ``[:, :gather_width]`` slice covering every allocated entry (the
    serving layer's power-of-two bucketing): the kernel walks the table,
    not the pool, so per-slot work is table-width x block_size."""
    W = mesh.shape[axis]
    cl = jnp.asarray(cur_len, jnp.int32).reshape(-1)
    tb = jnp.asarray(tables, jnp.int32)
    fn = functools.partial(_fd.flash_decode_paged_fused, axis=axis, W=W,
                           scale=scale)
    ins = (P(), P(axis, None, None, None), P(axis, None, None, None),
           P(), P())
    return jax.shard_map(fn, mesh=mesh, in_specs=ins, out_specs=P(),
                         axis_names={axis}, check_vma=False)(
        q, k_pool, v_pool, cl, tb)
