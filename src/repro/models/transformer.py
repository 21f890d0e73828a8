"""Layer-stack assembly: dense / MoE / hybrid(zamba2) / RWKV6 backbones.

Layers run under ``lax.scan`` over a stacked parameter tree (small HLO,
fast compile at 88 layers) with optional per-layer remat. The zamba2
hybrid scans groups of `attn_every` Mamba2 layers followed by ONE shared
attention+MLP block whose parameters are reused across groups (Zamba2's
shared-block design).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention, mamba2, mlp, moe, rwkv6
from repro.models.layers import apply_norm, norm_spec
from repro.models.module import stack_layer_specs


def _ckpt(fn, cfg):
    """Per-layer remat with the configured policy. 'dots' saves matmul
    outputs (recompute only elementwise chains): ~25% fewer backward
    FLOPs for ~2x activation memory — the §Perf remat iteration."""
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


# ------------------------------------------------------------------- specs
def layer_spec(cfg):
    if cfg.block == "attn_mlp":
        return {"ln1": norm_spec(cfg.d_model, cfg.norm),
                "attn": attention.attn_spec(cfg),
                "ln2": norm_spec(cfg.d_model, cfg.norm),
                "mlp": mlp.mlp_spec(cfg)}
    if cfg.block == "attn_moe":
        return {"ln1": norm_spec(cfg.d_model, cfg.norm),
                "attn": attention.attn_spec(cfg),
                "ln2": norm_spec(cfg.d_model, cfg.norm),
                "moe": moe.moe_spec(cfg)}
    if cfg.block == "mamba_hybrid":
        return {"ln1": norm_spec(cfg.d_model, cfg.norm),
                "mamba": mamba2.mamba_spec(cfg)}
    if cfg.block == "rwkv":
        return rwkv6.rwkv_spec(cfg)
    raise ValueError(cfg.block)


def stack_spec(cfg):
    spec: dict[str, Any] = {
        "layers": stack_layer_specs(layer_spec(cfg), cfg.n_layers)}
    if cfg.block == "mamba_hybrid" and cfg.attn_every:
        spec["shared_attn"] = {
            "ln1": norm_spec(cfg.d_model, cfg.norm),
            "attn": attention.attn_spec(cfg),
            "ln2": norm_spec(cfg.d_model, cfg.norm),
            "mlp": mlp.mlp_spec(cfg)}
    return spec


# ----------------------------------------------------------------- forward
def _attn_mlp_layer(p, x, cfg, positions):
    h = apply_norm(p["ln1"], x, cfg.norm)
    x = x + attention.apply_attn(p["attn"], h, cfg, positions=positions)
    h = apply_norm(p["ln2"], x, cfg.norm)
    if "moe" in p:
        y, aux = moe.apply_moe(p["moe"], h, cfg)
        return x + y, aux
    return x + mlp.apply_mlp(p["mlp"], h, cfg), jnp.float32(0.0)


def _mamba_layer(p, x, cfg):
    h = apply_norm(p["ln1"], x, cfg.norm)
    return x + mamba2.apply_mamba(p["mamba"], h, cfg)


def forward(params, x, cfg, *, positions=None):
    """x: (B, S, d) embedded input. Returns (x, aux_loss)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :]

    if cfg.block in ("attn_mlp", "attn_moe"):
        def body(carry, lp):
            x, aux = carry
            x, a = _attn_mlp_layer(lp, x, cfg, positions)
            return (x, aux + a), None
        body_fn = _ckpt(body, cfg)
        if cfg.scan_layers:
            (x, aux), _ = lax.scan(body_fn, (x, jnp.float32(0.0)),
                                   params["layers"])
        else:
            # unrolled: one HLO op per layer — used by the dry-run so
            # cost_analysis counts every layer (scan bodies count once)
            carry = (x, jnp.float32(0.0))
            for li in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[li], params["layers"])
                carry, _ = body_fn(carry, lp)
            x, aux = carry
        return x, aux

    if cfg.block == "mamba_hybrid":
        every = cfg.attn_every or cfg.n_layers
        n_groups, rem = divmod(cfg.n_layers, every)
        grouped = jax.tree.map(
            lambda a: a[: n_groups * every].reshape(
                (n_groups, every) + a.shape[1:]), params["layers"])
        tail = jax.tree.map(lambda a: a[n_groups * every:], params["layers"])
        shared = params["shared_attn"]

        def group_body(x, gp):
            def inner(x, lp):
                return _mamba_layer(lp, x, cfg), None
            inner_fn = _ckpt(inner, cfg)
            if cfg.scan_layers:
                x, _ = lax.scan(inner_fn, x, gp)
            else:
                for li in range(every):
                    x, _ = inner_fn(x, jax.tree.map(lambda a: a[li], gp))
            x, _ = _attn_mlp_layer(shared, x, cfg, positions)
            return x, None

        gb = _ckpt(group_body, cfg)
        if cfg.scan_layers:
            x, _ = lax.scan(gb, x, grouped)
        else:
            for gi in range(n_groups):
                x, _ = gb(x, jax.tree.map(lambda a: a[gi], grouped))
        if rem:
            def inner(x, lp):
                return _mamba_layer(lp, x, cfg), None
            if cfg.scan_layers:
                x, _ = lax.scan(inner, x, tail)
            else:
                for li in range(rem):
                    x, _ = inner(x, jax.tree.map(lambda a: a[li], tail))
        return x, jnp.float32(0.0)

    if cfg.block == "rwkv":
        def body(x, lp):
            x, _ = rwkv6.apply_rwkv_block(lp, x, cfg, state=None)
            return x, None
        body_fn = _ckpt(body, cfg)
        if cfg.scan_layers:
            x, _ = lax.scan(body_fn, x, params["layers"])
        else:
            for li in range(cfg.n_layers):
                x, _ = body_fn(x, jax.tree.map(lambda a: a[li],
                                               params["layers"]))
        return x, jnp.float32(0.0)

    raise ValueError(cfg.block)


# ------------------------------------------------------------------ decode
def init_caches(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked per-layer decode state."""
    if cfg.block in ("attn_mlp", "attn_moe"):
        one = attention.init_cache(cfg, batch, max_len, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), one)
    if cfg.block == "mamba_hybrid":
        every = cfg.attn_every or cfg.n_layers
        n_groups = cfg.n_layers // every
        m = mamba2.init_mamba_cache(cfg, batch, dtype)
        mstack = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), m)
        a = attention.init_cache(cfg, batch, max_len, dtype)
        astack = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n_groups,) + t.shape).copy(), a)
        return {"mamba": mstack, "attn": astack}
    if cfg.block == "rwkv":
        s = rwkv6.init_rwkv_state(cfg, batch, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), s)
    raise ValueError(cfg.block)


def init_paged_caches(cfg, batch: int, n_blocks: int, block_size: int,
                      dtype=jnp.bfloat16):
    """Stacked per-layer decode state, paged variant: attention KV lives
    in a shared block pool (layers, n_blocks, block_size, KVH, hd);
    recurrent (mamba/rwkv) state is inherently per-slot and stays
    (layers, batch, ...) — paging only applies to the KV axis."""
    if cfg.block in ("attn_mlp", "attn_moe"):
        one = attention.init_paged_cache(cfg, n_blocks, block_size, dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), one)
    if cfg.block == "mamba_hybrid":
        every = cfg.attn_every or cfg.n_layers
        n_groups = cfg.n_layers // every
        m = mamba2.init_mamba_cache(cfg, batch, dtype)
        mstack = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), m)
        a = attention.init_paged_cache(cfg, n_blocks, block_size, dtype)
        astack = jax.tree.map(
            lambda t: jnp.broadcast_to(t, (n_groups,) + t.shape).copy(), a)
        return {"mamba": mstack, "attn": astack}
    if cfg.block == "rwkv":
        return init_caches(cfg, batch, 0, dtype)   # no KV cache to page
    raise ValueError(cfg.block)


def copy_paged_block(cfg, caches, src, dst):
    """Copy pool block ``src`` to ``dst`` across every paged KV leaf (all
    layers) — the device half of the serving layer's copy-on-write.
    Recurrent state is untouched. src/dst may be traced scalars."""
    def cp(leaf):
        return leaf.at[:, dst].set(leaf[:, src])
    if cfg.block in ("attn_mlp", "attn_moe"):
        return jax.tree.map(cp, caches)
    if cfg.block == "mamba_hybrid":
        return {"mamba": caches["mamba"],
                "attn": jax.tree.map(cp, caches["attn"])}
    return caches


def _sel_state(active, old, new):
    """Per-slot predicated state update: slots with active=False keep
    their old recurrent state (continuous batching / chunked prefill).
    Leaves have batch at dim 0 here (inside the per-layer body)."""
    if active is None:
        return new
    return jax.tree.map(
        lambda o, n: jnp.where(
            active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o), old, new)


def decode(params, x, caches, cur_len, cfg, active=None, block_tables=None,
           bounded: bool = True):
    """One-token step. x: (B, 1, d). Returns (x, new_caches).

    ``cur_len``: scalar or per-slot (B,) lengths INCLUDING this token
    for active slots. ``active`` (B,) bool: slots that consume a token
    this step; inactive slots leave every cache/state leaf unchanged.
    ``block_tables`` (B, max_blocks) int32: paged KV — every attention
    cache access translates logical position -> (block, offset) through
    it (see attention.decode_attn_step; may be a gather-width leading
    slice of the full table). ``bounded``: distributed paged attention
    gathers through the table (bounded per-slot work) vs the masked
    whole-pool-shard oracle."""
    if cfg.block in ("attn_mlp", "attn_moe"):
        def body(x, inp):
            lp, cache = inp
            with jax.named_scope("attention"):
                h = apply_norm(lp["ln1"], x, cfg.norm)
                y, new_cache = attention.decode_attn_step(
                    lp["attn"], h, cache, cur_len, cfg, active=active,
                    block_tables=block_tables, bounded=bounded)
                x = x + y
            with jax.named_scope("mlp"):
                h = apply_norm(lp["ln2"], x, cfg.norm)
                if "moe" in lp:
                    y, _ = moe.apply_moe(lp["moe"], h, cfg)
                else:
                    y = mlp.apply_mlp_decode(lp["mlp"], h, cfg)
            return x + y, new_cache
        if cfg.scan_layers:
            x, new_caches = lax.scan(body, x, (params["layers"], caches))
            return x, new_caches
        outs = []
        for li in range(cfg.n_layers):
            inp = jax.tree.map(lambda a: a[li], (params["layers"], caches))
            x, nc = body(x, inp)
            outs.append(nc)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        return x, new_caches

    if cfg.block == "mamba_hybrid":
        every = cfg.attn_every or cfg.n_layers
        n_groups, rem = divmod(cfg.n_layers, every)
        grouped = jax.tree.map(
            lambda a: a[: n_groups * every].reshape(
                (n_groups, every) + a.shape[1:]), params["layers"])
        tail = jax.tree.map(lambda a: a[n_groups * every:], params["layers"])
        mcache = caches["mamba"]
        mgrp = jax.tree.map(
            lambda a: a[: n_groups * every].reshape(
                (n_groups, every) + a.shape[1:]), mcache)
        mtail = jax.tree.map(lambda a: a[n_groups * every:], mcache)
        shared = params["shared_attn"]

        def group_body(x, inp):
            gp, gc, ac = inp
            def inner(x, li):
                lp, lc = li
                h = apply_norm(lp["ln1"], x, cfg.norm)
                y, nc = mamba2.apply_mamba_decode(lp["mamba"], h, cfg=cfg,
                                                  cache=lc)
                return x + y, _sel_state(active, lc, nc)
            if cfg.scan_layers:
                x, ngc = lax.scan(inner, x, (gp, gc))
            else:
                accs = []
                for li in range(every):
                    x, nc = inner(x, jax.tree.map(lambda a: a[li], (gp, gc)))
                    accs.append(nc)
                ngc = jax.tree.map(lambda *xs: jnp.stack(xs), *accs)
            h = apply_norm(shared["ln1"], x, cfg.norm)
            y, nac = attention.decode_attn_step(shared["attn"], h, ac,
                                                cur_len, cfg, active=active,
                                                block_tables=block_tables,
                                                bounded=bounded)
            x = x + y
            h = apply_norm(shared["ln2"], x, cfg.norm)
            x = x + mlp.apply_mlp_decode(shared["mlp"], h, cfg)
            return x, (ngc, nac)

        if cfg.scan_layers:
            x, (nmg, nac) = lax.scan(group_body, x,
                                     (grouped, mgrp, caches["attn"]))
        else:
            gaccs = []
            for gi in range(n_groups):
                x, out = group_body(x, jax.tree.map(
                    lambda a: a[gi], (grouped, mgrp, caches["attn"])))
                gaccs.append(out)
            nmg, nac = jax.tree.map(lambda *xs: jnp.stack(xs), *gaccs)
        nm_flat = jax.tree.map(
            lambda a: a.reshape((n_groups * every,) + a.shape[2:]), nmg)
        if rem:
            def inner(x, li):
                lp, lc = li
                h = apply_norm(lp["ln1"], x, cfg.norm)
                y, nc = mamba2.apply_mamba_decode(lp["mamba"], h, cfg=cfg,
                                                  cache=lc)
                return x + y, _sel_state(active, lc, nc)
            if cfg.scan_layers:
                x, ntail = lax.scan(inner, x, (tail, mtail))
            else:
                taccs = []
                for li in range(rem):
                    x, nc = inner(x, jax.tree.map(lambda a: a[li],
                                                  (tail, mtail)))
                    taccs.append(nc)
                ntail = jax.tree.map(lambda *xs: jnp.stack(xs), *taccs)
            nm_flat = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), nm_flat, ntail)
        return x, {"mamba": nm_flat, "attn": nac}

    if cfg.block == "rwkv":
        def body(x, inp):
            lp, st = inp
            nx, nst = rwkv6.apply_rwkv_block(lp, x, cfg, state=st)
            return nx, _sel_state(active, st, nst)
        if cfg.scan_layers:
            x, new_states = lax.scan(body, x, (params["layers"], caches))
            return x, new_states
        saccs = []
        for li in range(cfg.n_layers):
            x, ns = body(x, jax.tree.map(lambda a: a[li],
                                         (params["layers"], caches)))
            saccs.append(ns)
        new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *saccs)
        return x, new_states

    raise ValueError(cfg.block)
