"""Weights from the seed, and the plain float32 reference forward.

Nothing here imports the program. The harness makes every weight with
:func:`make_params` (one jitted call, on the device, in the served
dtype) and hands the tree to the engine; the reference makes the same
values again, layer by layer, with :func:`layer_weights`, so it takes
nothing the program has made.

The reference is a decoder as the program implements it: pre-norm
RMSNorm with a learned scale, attention with grouped KV heads and
rotary embeddings over the whole head (the two halves of the head are
the rotation pairs), SwiGLU feed-forward, a final RMSNorm and an untied
output head. Where a configuration's published model departs from
that, its file lists the departure; the reference follows the program
there, because the comparison is of what the program computes.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

# leaf -> id folded into the key; a leaf's values depend only on the
# seed, this id and the layer, never on how the tree is traversed
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")
TOP_LEAVES = ("embed", "ln_f", "head")
_LEAF_ID = {n: i for i, n in enumerate(TOP_LEAVES + LAYER_LEAVES)}


@dataclasses.dataclass(frozen=True)
class Shape:
    """A decoder's sizes, read from a configuration file's published
    keys."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def from_config(cls, conf: dict) -> "Shape":
        return cls(n_layers=conf["num_hidden_layers"],
                   d_model=conf["hidden_size"],
                   n_heads=conf["num_attention_heads"],
                   n_kv_heads=conf["num_key_value_heads"],
                   d_ff=conf["intermediate_size"],
                   vocab=conf["vocab_size"],
                   rope_theta=float(conf["rope_theta"]),
                   norm_eps=float(conf["rms_norm_eps"]))

    def leaf_shape(self, leaf: str) -> tuple[int, ...]:
        d, f, v = self.d_model, self.d_ff, self.vocab
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        return {"embed": (v, d), "head": (v, d), "ln_f": (d,),
                "ln1": (d,), "ln2": (d,), "wq": (d, q), "wk": (d, kv),
                "wv": (d, kv), "wo": (q, d), "wg": (d, f), "wu": (d, f),
                "wd": (f, d)}[leaf]


def base_key(seed: int) -> jax.Array:
    """A key for any whole number up to 2**62: the low 31 bits seed it,
    the rest is folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, shape: Shape, leaf: str, layer, dtype):
    """One leaf's values. Norm scales are 1 + 0.1 N(0, 1), so a program
    that drops a scale is seen; the embedding is N(0, 1); every matrix
    is N(0, 1/fan_in), the head included, so logits are of order 1."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LEAF_ID[leaf]), layer)
    dims = shape.leaf_shape(leaf)
    z = jax.random.normal(k, dims, jnp.float32)
    if leaf in ("ln1", "ln2", "ln_f"):
        x = 1.0 + 0.1 * z
    elif leaf == "embed":
        x = z
    else:
        fan_in = shape.d_model if leaf == "head" else dims[0]
        x = z / math.sqrt(fan_in)
    return x.astype(dtype)


def layer_weights(key, shape: Shape, layer, dtype=jnp.bfloat16) -> dict:
    return {n: _leaf(key, shape, n, layer, dtype) for n in LAYER_LEAVES}


def top_weights(key, shape: Shape, dtype=jnp.bfloat16) -> dict:
    return {n: _leaf(key, shape, n, 0, dtype) for n in TOP_LEAVES}


def program_tree(top: dict, layers: dict) -> dict:
    """The program's parameter layout (``repro.models.lm.lm_spec`` for
    a dense ``attn_mlp`` decoder), from the reference's leaves. Layer
    leaves carry a leading layer dim."""
    return {
        "embed": {"table": top["embed"]},
        "head": {"table": top["head"]},
        "ln_f": {"scale": top["ln_f"]},
        "backbone": {"layers": {
            "ln1": {"scale": layers["ln1"]},
            "ln2": {"scale": layers["ln2"]},
            "attn": {n: layers[n] for n in ("wq", "wk", "wv", "wo")},
            "mlp": {n: layers[n] for n in ("wg", "wu", "wd")},
        }},
    }


def make_params(seed: int, shape: Shape, dtype, out_shardings=None):
    """Every weight from ``seed`` in ONE jitted call, in ``dtype``,
    born with ``out_shardings`` (a tree like :func:`program_tree`'s)."""
    # the key is an argument, not a constant of the program, so that one
    # compiled program serves every seed from the persistent cache
    def init(key):
        layers = jax.vmap(lambda i: layer_weights(key, shape, i, dtype))(
            jnp.arange(shape.n_layers))
        return program_tree(top_weights(key, shape, dtype), layers)
    return jax.jit(init, out_shardings=out_shardings)(base_key(seed))


# ------------------------------------------------------------ reference
def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x (T, heads, hd), pos (T,): rotate (x1, x2) pairs, where x1 is
    the first half of the head and x2 the second."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def fp8_matmul(x, w):
    """x @ w with both sides in float8_e4m3fn: per-row scales on x,
    per-output-column scales on w, float32 accumulation. The control:
    the precision below the served bfloat16."""
    f8 = jnp.float8_e4m3fn
    top = float(jnp.finfo(f8).max)
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12) / top
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / top
    xq = (x / sx).astype(f8).astype(jnp.float32)
    wq = (w / sw).astype(f8).astype(jnp.float32)
    return (xq @ wq) * sx * sw


def _mm(x, w, fp8: bool):
    return fp8_matmul(x, w) if fp8 else x @ w


def layer_forward(x, w, pos, seg, shape: Shape, fp8: bool = False,
                  q_chunk: int = 512):
    """One decoder layer over a packed row. x (T, d) float32; pos (T,)
    position within its sequence; seg (T,) sequence id (-1 = padding).
    Attention is causal within a sequence, computed in query chunks."""
    T = x.shape[0]
    H, KVH, hd = shape.n_heads, shape.n_kv_heads, shape.head_dim
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    h = _rmsnorm(x, w["ln1"], shape.norm_eps)
    q = _rope(_mm(h, w["wq"], fp8).reshape(T, H, hd), pos, shape.rope_theta)
    k = _rope(_mm(h, w["wk"], fp8).reshape(T, KVH, hd), pos,
              shape.rope_theta)
    v = _mm(h, w["wv"], fp8).reshape(T, KVH, hd)
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    cq = min(q_chunk, T)
    nq = T // cq

    def chunk(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * cq, cq)
        ps = jax.lax.dynamic_slice_in_dim(pos, i * cq, cq)
        ss = jax.lax.dynamic_slice_in_dim(seg, i * cq, cq)
        s = jnp.einsum("qhd,khd->hqk", qs, k) / math.sqrt(hd)
        ok = (ss[:, None] == seg[None, :]) & (ps[:, None] >= pos[None, :])
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)     # padding rows see nothing
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(chunk, jnp.arange(nq)).reshape(T, H * hd)
    x = x + _mm(o, w["wo"], fp8)
    h = _rmsnorm(x, w["ln2"], shape.norm_eps)
    g = _mm(h, w["wg"], fp8)
    u = _mm(h, w["wu"], fp8)
    return x + _mm(jax.nn.silu(g) * u, w["wd"], fp8)


def pack(seqs: list[list[int]], multiple: int = 512):
    """Pack token lists into one row, padded to a power of two (at least
    ``multiple``): (tokens, pos, seg, starts)."""
    n = sum(len(s) for s in seqs)
    T = multiple
    while T < n:
        T *= 2
    tokens = np.zeros(T, np.int32)
    pos = np.zeros(T, np.int32)
    seg = np.full(T, -1, np.int32)
    starts, at = [], 0
    for i, s in enumerate(seqs):
        starts.append(at)
        tokens[at:at + len(s)] = s
        pos[at:at + len(s)] = np.arange(len(s))
        seg[at:at + len(s)] = i
        at += len(s)
    return tokens, pos, seg, starts


class Reference:
    """The float32 forward at the highest matmul precision, over a
    packed row, one layer at a time so that it fits beside nothing:
    each layer's weights are made from the seed, used and dropped.
    ``fp8=True`` is the control: the same forward with every
    projection in float8."""

    def __init__(self, shape: Shape, seed: int, device=None,
                 dtype=jnp.bfloat16, fp8: bool = False):
        self.shape, self.dtype, self.fp8 = shape, dtype, fp8
        self.device = device or jax.devices()[0]
        # the key is an argument of every program, so that the programs
        # are the same for every seed and come from the persistent cache
        self.key = jax.device_put(base_key(seed), self.device)
        sh = shape

        def layer(x, pos, seg, i, key):
            with jax.default_matmul_precision("highest"):
                w = layer_weights(key, sh, i, dtype)
                return layer_forward(x, w, pos, seg, sh, fp8=fp8)

        def embed(tokens, key):
            table = _leaf(key, sh, "embed", 0, dtype)
            return table[tokens].astype(jnp.float32)

        def final(x, key):
            t = top_weights(key, sh, dtype)
            return _rmsnorm(x, t["ln_f"].astype(jnp.float32), sh.norm_eps)

        self._layer = jax.jit(layer)
        self._embed = jax.jit(embed)
        self._final = jax.jit(final)
        self._head = jax.jit(self._head_stats)

    def _head_stats(self, x, targets, key):
        """Per row of x (n, d): the best logit, its token, and the logit
        of ``targets``; float32 at the highest precision, or float8
        for the control."""
        head = _leaf(key, self.shape, "head", 0,
                     self.dtype).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            logits = _mm(x, head.T, self.fp8)
        at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
        return logits.max(axis=1), logits.argmax(axis=1), at

    def hidden(self, tokens, pos, seg):
        """Final-normed hidden states (T, d) of a packed row."""
        put = lambda a: jax.device_put(jnp.asarray(a), self.device)  # noqa: E731
        tokens, pos, seg = put(tokens), put(pos), put(seg)
        x = self._embed(tokens, self.key)
        for i in range(self.shape.n_layers):
            x = self._layer(x, pos, seg, jnp.int32(i), self.key)
        return self._final(x, self.key)

    def head_stats(self, h, rows, targets, block: int = 1024):
        """(best, argmax, logit at target) for ``rows`` of hidden
        states h, in blocks of rows so the logits never exist whole."""
        rows = np.asarray(rows, np.int32)
        targets = np.asarray(targets, np.int32)
        out = [[], [], []]
        for a in range(0, len(rows), block):
            r = rows[a:a + block]
            t = targets[a:a + block]
            pad = block - len(r)
            r = np.pad(r, (0, pad))
            t = np.pad(t, (0, pad))
            got = self._head(h[jnp.asarray(r)], jnp.asarray(t), self.key)
            for o, g in zip(out, got):
                o.append(np.asarray(g)[:block - pad])
        return tuple(np.concatenate(o) if o else np.zeros(0) for o in out)


def served_rows(prompts, outputs, starts):
    """For packed (prompt + output[:-1]) rows: the row whose logits chose
    each served token, and that token."""
    rows, toks = [], []
    for p, o, s in zip(prompts, outputs, starts):
        for j, t in enumerate(o):
            rows.append(s + len(p) - 1 + j)
            toks.append(t)
    return np.asarray(rows, np.int32), np.asarray(toks, np.int32)


def served_gaps(ref: Reference, prompts, outputs, control: Reference = None):
    """Run the reference once over each prompt with its served tokens.
    Returns, per served token, how far the reference's logit of that
    token lies below the reference's best logit at that position; and,
    with ``control``, the same gap for the token the control puts first
    at each position of the same sequences."""
    seqs = [list(p) + list(o[:-1]) for p, o in zip(prompts, outputs)]
    tokens, pos, seg, starts = pack(seqs)
    rows, toks = served_rows(prompts, outputs, starts)
    h = ref.hidden(tokens, pos, seg)
    best, _, at = ref.head_stats(h, rows, toks)
    gaps = best - at
    if control is None:
        return gaps, None
    hc = control.hidden(tokens, pos, seg)
    _, ctrl_top, _ = control.head_stats(hc, rows, toks)
    del hc
    best2, _, at2 = ref.head_stats(h, rows, ctrl_top)
    return gaps, best2 - at2
