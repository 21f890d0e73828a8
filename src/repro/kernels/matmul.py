"""Tiled local GEMM Pallas kernel (MXU-aligned BlockSpec VMEM tiling).

The building block the fused AG+GEMM kernel extends. Blocks are
(bm, bk) × (bk, bn) with bm/bn multiples of 128 (MXU systolic dims) and a
fp32 VMEM accumulator revisited across the K grid dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import jax_compat


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(a, b, *, bm: int = 256, bk: int = 512, bn: int = 256,
           interpret=None):
    """C = A @ B. a: (M, K), b: (K, N). Tile sizes clamp to the shape."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    nm, nk, nn = M // bm, K // bk, N // bn
    if interpret is None:
        interpret = jax_compat.default_interpret()
    grid = (nm, nn, nk)
    return pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=jax_compat.pallas_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, b)
