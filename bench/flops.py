"""Model FLOPs of a dense decoder, from its shapes.

A token at position p (0-based) costs two FLOPs per multiply-add of
every projection it passes through, the output head included and the
input embedding (a lookup) excluded, plus attention over the p + 1
positions it sees: QK^T and PV, each 2 * heads * head_dim per position,
in every layer.
"""
from __future__ import annotations


def matmul_params(shape) -> int:
    """Multiply-adds per token in the projections and the head."""
    d, f, hd = shape.d_model, shape.d_ff, shape.head_dim
    q, kv = shape.n_heads * hd, shape.n_kv_heads * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return shape.n_layers * per_layer + shape.vocab * d


def attention_per_position(shape) -> int:
    """FLOPs per attended position per token, over all layers."""
    return 4 * shape.n_layers * shape.n_heads * shape.head_dim


def token_flops(shape, pos: int) -> int:
    return 2 * matmul_params(shape) + attention_per_position(shape) * (pos + 1)


def span_flops(shape, start: int, stop: int) -> int:
    """FLOPs of the tokens at positions start .. stop - 1."""
    n = max(0, stop - start)
    seen = (start + 1 + stop) * n // 2          # sum of (p + 1)
    return 2 * matmul_params(shape) * n + attention_per_position(shape) * seen
