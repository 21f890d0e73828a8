"""Top-level LM: embeddings → backbone stack → head; loss; step functions.

Handles all assigned families:
* dense / moe LMs: token embeddings, causal.
* vlm (paligemma): precomputed patch embeddings (stub frontend per the
  assignment) projected and prepended; prefix-LM mask.
* audio (hubert): precomputed frame embeddings (stub frontend) projected;
  encoder-only (bidirectional), per-frame classification head.
* hybrid / ssm: same embedding/head, different backbone.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed import context as dctx
from repro.distributed.sharding_rules import constrain
from repro.models import transformer
from repro.models.layers import (apply_embed, apply_norm, apply_unembed,
                                 embed_spec, norm_spec)
from repro.models.module import (Param, axes_tree, init_tree, is_param,
                                 shapes_tree)


def lm_spec(cfg):
    spec: dict[str, Any] = {
        "embed": embed_spec(cfg.vocab_size, cfg.d_model),
        "backbone": transformer.stack_spec(cfg),
        "ln_f": norm_spec(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        spec["head"] = {"table": Param((cfg.vocab_size, cfg.d_model),
                                       init="scaled",
                                       axes=("vocab", "embed"))}
    if cfg.frontend_dim:
        spec["frontend_proj"] = {
            "kernel": Param((cfg.frontend_dim, cfg.d_model), init="scaled",
                            axes=(None, "embed"))}
    if cfg.block == "rwkv":
        spec["ln_in"] = norm_spec(cfg.d_model, "layernorm")
    # every leaf is stored in cfg.param_dtype: float32 master weights for
    # training, cfg.dtype for a server, which keeps no master copy
    return jax.tree.map(
        lambda p: dataclasses.replace(p, dtype=cfg.param_dtype), spec,
        is_leaf=is_param)


def init_params(key, cfg):
    return init_tree(key, lm_spec(cfg))


def init_params_sharded(key, cfg, rules):
    """``init_params`` under ``jax.jit`` with each leaf's rules sharding
    as its output sharding: leaves are born sharded on the rules' mesh
    in ``cfg.param_dtype``, so no whole float32 tree is ever built and
    nothing lands on the first device by default."""
    spec = lm_spec(cfg)
    shardings = rules.shardings(axes_tree(spec), shapes_tree(spec))
    return jax.jit(lambda k: init_tree(k, spec),
                   out_shardings=shardings)(key)


def param_axes(cfg):
    return axes_tree(lm_spec(cfg))


# ---------------------------------------------------------------- embedding
def embed_inputs(params, batch, cfg):
    """batch: dict with 'tokens' (B,S) and/or 'patches'/'frames' (B,P,fd).
    Returns (x (B,L,d), positions (1 or B, L), label_offset)."""
    ctx = dctx.current()
    parts = []
    if cfg.family == "audio":
        x = jnp.einsum("bpf,fd->bpd", batch["frames"].astype(cfg.dtype),
                       params["frontend_proj"]["kernel"].astype(cfg.dtype))
        parts.append(x)
    else:
        if cfg.family == "vlm" and "patches" in batch:
            p = jnp.einsum("bpf,fd->bpd", batch["patches"].astype(cfg.dtype),
                           params["frontend_proj"]["kernel"].astype(cfg.dtype))
            parts.append(p)
        # gather in the table dtype (f32) and cast AFTER the sharding
        # constraint: the masked-gather psum over `data` then stays f32 —
        # CPU-XLA's AllReducePromotion CHECK-crashes on bf16 all-reduces.
        parts.append(apply_embed(params["embed"], batch["tokens"],
                                 jnp.float32))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    if cfg.block == "rwkv":
        x = apply_norm(params["ln_in"], x, "layernorm")
    x = constrain(x, ctx.rules, "batch", "seq", None).astype(cfg.dtype)
    prefix = x.shape[1] - (batch["tokens"].shape[1]
                           if "tokens" in batch and cfg.family != "audio"
                           else x.shape[1])
    positions = jnp.arange(x.shape[1])[None, :]
    return x, positions, prefix


def logits_fn(params, x, cfg):
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["head"]["table"])
    ctx = dctx.current()
    logits = apply_unembed({"table": table}, x, dtype=jnp.bfloat16)
    # NOTE: "seq" and "vocab" both map to `model` — naming both would
    # degrade vocab to replicated and make XLA gather the whole unembed
    # table (394MB+) AND materialize full-V logits per chip (measured:
    # §Perf phi3 iteration 2). Keep V sharded; gather seq once instead.
    return constrain(logits, ctx.rules, "batch", None, "vocab")


def forward(params, batch, cfg):
    """Full forward: returns (logits (B,L,V), aux_loss)."""
    x, positions, _ = embed_inputs(params, batch, cfg)
    x, aux = transformer.forward(params["backbone"], x, cfg,
                                 positions=positions)
    x = apply_norm(params["ln_f"], x, cfg.norm)
    return logits_fn(params, x, cfg), aux


# -------------------------------------------------------------------- loss
def cross_entropy(logits, labels, mask=None, z_loss: float = 1e-4):
    """Streamed CE in fp32 with z-loss; labels -100 are ignored."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(
        lf, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    ce = lse - gold
    if z_loss:
        ce = ce + z_loss * jnp.square(lse)
    valid = (labels >= 0)
    if mask is not None:
        valid = valid & (mask > 0)
    ce = jnp.where(valid, ce, 0.0)
    n = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(ce) / n


def loss_fn(params, batch, cfg, aux_weight: float = 0.01):
    logits, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    if logits.shape[1] != labels.shape[1]:   # vlm prefix: no loss on patches
        pad = logits.shape[1] - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.full(labels.shape[:1] + (pad,), -100, labels.dtype), labels],
            axis=1)
    loss = cross_entropy(logits, labels)
    return loss + aux_weight * aux, {"ce": loss, "aux": aux}


# ------------------------------------------------------------------ decode
def init_decode_state(params, cfg, batch: int, max_len: int):
    """Decode state for continuous batching: ``cur_len`` is a per-slot
    (B,) position vector, NOT a shared scalar — each cache slot advances
    independently, so the engine can admit a request into a freed slot
    mid-run and its prompt starts at position 0 while neighbours keep
    decoding at their own positions."""
    return {"caches": transformer.init_caches(cfg, batch, max_len, cfg.dtype),
            "cur_len": jnp.zeros((batch,), jnp.int32)}


def init_paged_decode_state(params, cfg, batch: int, n_blocks: int,
                            block_size: int, max_blocks: int):
    """Paged decode state: KV lives in a shared pool of
    ``n_blocks`` x ``block_size`` blocks; ``block_tables`` (B, max_blocks)
    maps each slot's logical chunks to pool blocks (-1 = unallocated) and
    rides in the jitted state so every decode step translates positions
    through it. The serving allocator (serving.kv_cache.CachePool) owns
    the host-side table/refcount bookkeeping and mirrors the table in.
    """
    return {"caches": transformer.init_paged_caches(cfg, batch, n_blocks,
                                                    block_size, cfg.dtype),
            "cur_len": jnp.zeros((batch,), jnp.int32),
            "block_tables": jnp.full((batch, max_blocks), -1, jnp.int32)}


def paged_state_axes(state):
    """Logical axes of a paged decode state (arrays or shapes): the
    layer-stacked KV pool leaves (layers, n_blocks, block_size, KVH, hd)
    shard their block dim on ``kv_blocks``, the layout the paged
    attention regions expect; positions, tables and recurrent state are
    replicated."""
    def axes(path, x):
        if str(getattr(path[-1], "key", "")) in ("k", "v") and x.ndim == 5:
            return ("layers", "kv_blocks", None, None, None)
        return (None,) * x.ndim
    return jax.tree_util.tree_map_with_path(axes, state)


def init_paged_decode_state_sharded(cfg, batch: int, n_blocks: int,
                                    block_size: int, max_blocks: int,
                                    rules):
    """``init_paged_decode_state`` under ``jax.jit``, each leaf born with
    its rules sharding (:func:`paged_state_axes`)."""
    def init():
        return init_paged_decode_state(None, cfg, batch, n_blocks,
                                       block_size, max_blocks)

    shapes = jax.eval_shape(init)
    shardings = rules.shardings(paged_state_axes(shapes), shapes)
    return jax.jit(init, out_shardings=shardings)()


def set_slot_len(state, slot: int, n: int):
    """Set one slot's position counter (paged admission: a prefix-cache
    hit starts the slot mid-prompt, at the first non-reused token)."""
    return {**state, "cur_len": state["cur_len"].at[slot].set(n)}


def copy_cache_block(state, cfg, src, dst):
    """Device half of copy-on-write: clone pool block src -> dst across
    all layers' paged KV leaves."""
    return {**state,
            "caches": transformer.copy_paged_block(cfg, state["caches"],
                                                   src, dst)}


def reset_slot(state, slot: int):
    """Zero one batch slot's cache/state (continuous-batching admission).
    Every cache leaf has batch at dim 1 (stacked layers at dim 0) except
    cur_len (dim 0)."""
    def zero_slot(x):
        if x.ndim >= 2:
            return x.at[:, slot].set(0)
        return x
    caches = jax.tree.map(zero_slot, state["caches"])
    return {"caches": caches,
            "cur_len": state["cur_len"].at[slot].set(0)}


def reset_slot_paged(state, cfg, slot: int):
    """Paged admission reset: zero the slot's RECURRENT state only
    (mamba/rwkv leaves, batch at dim 1). Paged KV blocks need no zeroing
    — stale block contents sit beyond cur_len until overwritten, and the
    validity mask hides them."""
    def zero_slot(x):
        if x.ndim >= 2:
            return x.at[:, slot].set(0)
        return x
    caches = state["caches"]
    if cfg.block == "mamba_hybrid":
        caches = {"mamba": jax.tree.map(zero_slot, caches["mamba"]),
                  "attn": caches["attn"]}
    elif cfg.block == "rwkv":
        caches = jax.tree.map(zero_slot, caches)
    return {**state, "caches": caches,
            "cur_len": state["cur_len"].at[slot].set(0)}


def release_slot_paged(state, slot: int):
    """Preemption reset: zero a slot's position counter the moment its
    blocks are freed, not at the next admission. The slot sits inactive
    in every jitted step until re-admission (the active mask freezes
    it) and ``alloc()`` runs the full ``reset_slot_paged`` then — the
    length is the only field that must not dangle meanwhile, because
    the slot's table row goes to -1 immediately and a stale ``cur_len``
    would point past blocks now owned by other slots."""
    return set_slot_len(state, slot, 0)


def decode_step(params, token, state, cfg, active=None,
                gather_width: int | None = None, bounded: bool = True):
    """token: (B, 1) int32; one autoregressive step. Returns
    (logits (B, 1, V), new_state).

    ``active`` (B,) bool — slots that consume a token this step. An
    inactive slot's caches, recurrent states, and ``cur_len`` entry are
    left byte-identical, so heterogeneous slots (mid-prefill, decoding,
    idle) can share one jitted step. ``active=None`` means all slots
    step (the lockstep special case).

    Gather-width bucketing contract (paged states only): ``gather_width``
    is a STATIC compile-time width — the attention paths see only the
    leading ``[:, :gather_width]`` slice of the block table, so per-slot
    paged-attention work is gather_width x block_size positions instead
    of the full pool shard. The caller must guarantee the slice covers
    every allocated (>= 0) table entry of every active slot (the serving
    layer uses ``CachePool.gather_width()``: the live
    ``max_blocks_in_use`` watermark padded UP to the next power of two,
    clamped to ``max_blocks``). Because each distinct width is a new jit
    specialization, padding to power-of-two buckets bounds recompiles at
    log2(max_blocks) over an engine's lifetime; ``None`` means the full
    table width (no recompile coupling, maximum work). The returned
    state always carries the FULL table. ``bounded`` selects the
    distributed paged work model (table-gather vs masked-pool oracle);
    single-device paged decode reads the whole pool once when it holds
    at most batch x gather_width blocks, and gathers the table slice
    otherwise (``flash_decode.paged_view``)."""
    ctx = dctx.current()
    if active is None:
        cur_len = state["cur_len"] + 1        # includes the new token
    else:
        active = jnp.asarray(active)
        cur_len = state["cur_len"] + active.astype(jnp.int32)
    # decode x layout: d-model dim sharded over `data`, MATCHING the FSDP
    # weight shards — every projection becomes a local partial dot + a
    # tiny (B,1,out) psum, and the fp32 master weights are never
    # all-gathered (weights-stationary decode; batch dim is replicated —
    # (B,1,d) activations are negligible next to the KV caches, which
    # stay batch-sharded). Measured: 0.76 GB -> ~0.02 GB per chip per
    # step on mistral-large (§Perf A4).
    # named scopes (embed, attention, kv_write, mlp, lm_head, sample)
    # name the source region of each op in a profile; the compiled
    # program is the same
    with jax.named_scope("embed"):
        x = apply_embed(params["embed"], token, jnp.float32)
        x = constrain(x, ctx.rules, None, None, "embed").astype(cfg.dtype)
        if cfg.block == "rwkv":
            x = apply_norm(params["ln_in"], x, "layernorm")
    bt = state.get("block_tables")
    btg = bt if (bt is None or gather_width is None) \
        else bt[:, :gather_width]
    x, caches = transformer.decode(params["backbone"], x, state["caches"],
                                   cur_len, cfg, active=active,
                                   block_tables=btg, bounded=bounded)
    with jax.named_scope("lm_head"):
        x = apply_norm(params["ln_f"], x, cfg.norm)
        logits = logits_fn(params, x, cfg)
    new_state = {"caches": caches, "cur_len": cur_len}
    if bt is not None:
        new_state["block_tables"] = bt
    return logits, new_state


def decode_chunk(params, tokens, counts, state, cfg,
                 gather_width: int | None = None, bounded: bool = True):
    """Chunked batched prefill: consume up to C tokens per slot in ONE
    jitted call (a ``lax.scan`` of ``decode_step`` over the chunk, so
    dispatch/launch overhead is paid once per tick, not per token).

    tokens: (B, C) int32 — each slot's next tokens, left-aligned;
    counts: (B,) int32  — how many of the C are real for each slot
                          (0 = idle slot, 1 = plain decode step,
                          2..C = prompt chunk).
    Returns (logits (B, 1, V) from each slot's LAST consumed token,
    new_state). Slots with count 0 return zero logits.

    ``gather_width``/``bounded`` follow the :func:`decode_step`
    gather-width bucketing contract; the width must cover the table
    entries allocated for the WHOLE chunk (the serving layer allocates
    blocks for the tick before computing the bucket).
    """
    B, C = tokens.shape
    V = cfg.vocab_size

    def body(carry, j):
        st, logits = carry
        act = j < counts
        lg, st = decode_step(params, tokens[:, j][:, None], st, cfg,
                             active=act, gather_width=gather_width,
                             bounded=bounded)
        logits = jnp.where(act[:, None, None], lg.astype(logits.dtype),
                           logits)
        return (st, logits), None

    logits0 = jnp.zeros((B, 1, V), jnp.float32)
    (state, logits), _ = jax.lax.scan(body, (state, logits0),
                                      jnp.arange(C))
    return logits, state


def decode_multi(params, token, state, cfg, *, steps: int, budgets,
                 sample_fn, gather_width: int | None = None,
                 bounded: bool = True):
    """Fused multi-token decode megatick: ``steps`` autoregressive
    decode steps in ONE jitted program, with sampling DEVICE-RESIDENT —
    each scan iteration samples its next token in-graph and feeds it to
    the following step through the carry, so the host neither ships
    K x (B, V) logits down nor re-uploads tokens between steps. The
    paper's Kernel Launch Overhead Tax and the per-token bulk
    host<->device synchronization both collapse to once per megatick.

    token:   (B, 1) int32 — each slot's last sampled (or final prompt)
             token, the input to the first step.
    steps:   STATIC scan length K (a jit specialization per value; the
             serving layer buckets it to powers of two like the prefill
             chunk, bounding recompiles at log2(decode_steps)).
    budgets: (B,) int32 — how many of the K steps each slot runs. A
             slot past its budget (it hit ``max_new_tokens``/``max_len``
             mid-megatick, or the pool could not reserve its blocks) is
             FROZEN byte-identically via the ``active`` mask, exactly
             like an idle slot in :func:`decode_step`; 0 freezes the
             whole megatick for that slot.
    sample_fn: ``(logits (B, 1, V), j) -> (B, 1) int32`` — in-graph
             sampler for scan step ``j``. The serving layer passes
             either a plain argmax or the seeded batch sampler with
             (seed, rid, token-index)-folded keys, ``j`` offsetting the
             per-slot token index so streams stay
             scheduling-independent and preemption-safe.

    Returns (tokens (B, steps) int32, new_state). Row b is valid up to
    ``budgets[b]`` tokens; past-budget entries repeat the slot's last
    valid token and must be ignored by the caller.

    ``gather_width``/``bounded`` follow the :func:`decode_step`
    contract; the width must cover every block the WHOLE megatick
    writes (the serving layer reserves all K steps' blocks before
    computing the bucket).
    """
    def body(carry, j):
        st, tok = carry
        act = j < budgets
        logits, st = decode_step(params, tok, st, cfg, active=act,
                                 gather_width=gather_width,
                                 bounded=bounded)
        with jax.named_scope("sample"):
            nxt = sample_fn(logits, j)
        tok = jnp.where(act[:, None], nxt, tok)
        return (st, tok), tok[:, 0]

    (state, _), out = jax.lax.scan(body, (state, token),
                                   jnp.arange(steps))
    return out.T, state


def decode_mixed(params, tokens, token0, prefill_lens, emit_from, totals,
                 state, cfg, *, steps, sample_fn,
                 gather_width: int | None = None, bounded: bool = True):
    """Mixed prefill+decode megatick: ONE jitted scan in which every
    slot carries a per-step ROLE — consume the next prompt token, or
    sample-and-feed-back — so chunked prefill piggybacks on the fused
    decode dispatch instead of bailing the whole batch out to
    one-launch-per-token whenever any slot is mid-prompt (the paper's
    kernel-launch tax, which :func:`decode_multi` only eliminated for
    pure-decode batches). Sampling stays device-resident; only the
    (B, steps) sampled-token ids return to host.

    Per slot ``b``, scan step ``j`` runs exactly one of three roles:

    * ``j < prefill_lens[b]`` — PREFILL: the step consumes prompt token
      ``tokens[b, j]`` (left-aligned; the engine fills the row with the
      slot's next unconsumed effective-prompt tokens);
    * ``prefill_lens[b] <= j < totals[b]`` — DECODE: the step consumes
      the carry token (the previously sampled one; ``token0[b]`` seeds
      it for slots that enter the megatick already decoding);
    * ``j >= totals[b]`` — FROZEN: the ``active`` mask leaves caches,
      recurrent state, and ``cur_len`` byte-identical, exactly like an
      idle slot in :func:`decode_step`.

    Sampling fires on steps ``emit_from[b] <= j < totals[b]``. The
    engine sets ``emit_from`` to ``prefill_lens - 1`` (floored at 0)
    for slots whose prompt COMPLETES inside this megatick — so a slot
    that consumes its last prompt token at step j samples its first
    output token at step j, not next tick, exactly matching the
    unfused path's emit-on-prefill-completion — and to ``totals`` for
    slots still mid-prompt at megatick end (no emission). Pure-decode
    slots get ``prefill_lens == 0`` and ``emit_from == 0``:
    :func:`decode_multi` semantics as the degenerate case.

    tokens:       (B, S) int32 prompt tokens, left-aligned per row.
    token0:       (B, 1) int32 initial carry (a decoding slot's last
                  sampled token; ignored for rows that start in the
                  prefill role).
    prefill_lens: (B,) int32 — prompt tokens this megatick consumes.
    emit_from:    (B,) int32 — first step whose logits are sampled.
    totals:       (B,) int32 — total steps (= KV writes) per slot; the
                  caller must have reserved blocks for ALL of them
                  (``CachePool.reserve`` covers prompt and decode
                  writes alike).
    steps:        STATIC scan length S >= max(totals) (pow2-bucketed by
                  the serving layer, bounding recompiles).
    sample_fn:    ``(logits (B, 1, V), j) -> (B, 1) int32`` in-graph
                  sampler; the engine's closure offsets each slot's
                  (seed, rid, token-index) key fold by ``j -
                  emit_from``, so emitted streams stay scheduling-
                  independent — token-identical to the single-step
                  engine whatever the prefill/decode interleaving.

    Returns (out (B, steps) int32, new_state). Row b's emitted tokens
    are ``out[b, emit_from[b]:totals[b]]``; entries outside that span
    are stale carry values and must be ignored.

    ``gather_width``/``bounded`` follow the :func:`decode_step`
    contract; the width must cover every block the whole megatick
    writes (prompt chunks included).
    """
    def body(carry, j):
        st, tok = carry
        act = j < totals
        inp = jnp.where((j < prefill_lens)[:, None],
                        tokens[:, j][:, None], tok)
        logits, st = decode_step(params, inp, st, cfg, active=act,
                                 gather_width=gather_width,
                                 bounded=bounded)
        emit = (j >= emit_from) & act
        with jax.named_scope("sample"):
            nxt = sample_fn(logits, j)
        tok = jnp.where(emit[:, None], nxt, tok)
        return (st, tok), tok[:, 0]

    (state, _), out = jax.lax.scan(body, (state, token0),
                                   jnp.arange(steps))
    return out.T, state
