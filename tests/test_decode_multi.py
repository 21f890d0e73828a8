"""Fused multi-token decode megaticks: per-PR (fast tier) coverage.

``Engine(decode_steps=K)`` runs K decode steps per jitted dispatch with
sampling DEVICE-RESIDENT (``lm.decode_multi``): each scan step's sampled
token feeds the next step through the carry, and only (B, K) token ids
return to host. The contract under test:

* ``decode_steps=1`` is the byte-identical regression anchor — the
  exact single-step code path, pinned tick/dispatch counts on the
  staggered suite;
* K > 1 is TOKEN-identical to the single-step engine for greedy AND
  the seeded temperature sampler — including a slot that exhausts
  ``max_new_tokens`` at step j < K (frozen mid-megatick), preemption at
  megatick boundaries, and sliding-window reclaim;
* steady-state decode costs <= 1/K dispatches per token, counted from
  the engine's structural counters, not wall-clock;
* one tiny 8-fake-device subprocess promotes the bsp-mode battery
  check (``check_engine_megatick_bsp_small``) into the per-PR tier.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models import lm
from repro.serving.engine import Engine, Request
from repro.testing.decode_reference import reference_generate


def _setup(n_layers=2):
    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=n_layers)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run(params, cfg, prompts, *, K, sampler="greedy", max_new=9,
         n_blocks=None, batch=2, max_len=64, prefill_chunk=4,
         block_size=16, stagger=0):
    eng = Engine(params, cfg, batch=batch, max_len=max_len,
                 prefill_chunk=prefill_chunk, sampler=sampler, seed=7,
                 block_size=block_size, n_blocks=n_blocks, decode_steps=K)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=max_new,
                           temp=1.0), at_tick=i * stagger)
    done = eng.run()
    assert len(done) == len(prompts), (K, sampler, len(done))
    return {r.rid: r.out_tokens for r in done}, eng


@pytest.mark.parametrize("sampler", ["greedy", "temperature"])
def test_megatick_token_identity_vs_single_step(sampler):
    """K in {1, 2, 8}: the megatick engine's streams are token-identical
    to the single-step engine's under both samplers, with strictly fewer
    decode dispatches at K > 1."""
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (7, 3, 5)]
    base, eng1 = _run(params, cfg, prompts, K=1, sampler=sampler)
    d1 = eng1.decode_dispatch_count
    assert d1 > 0
    for K in (2, 8):
        out, engK = _run(params, cfg, prompts, K=K, sampler=sampler)
        assert out == base, (K, sampler, out, base)
        assert engK.decode_dispatch_count < d1, (K, sampler)
        assert engK.dispatch_count < eng1.dispatch_count, (K, sampler)


def test_decode_steps_one_is_byte_identical_anchor():
    """Explicit ``Engine(decode_steps=1)`` reproduces the pre-megatick
    engine byte-for-byte on the staggered suite: the pinned
    tick/dispatch counts (recorded from the pre-scheduler-subsystem
    engine) AND the solo-run token streams."""
    cfg, params = _setup()
    anchor = {1: (27, 27), 4: (15, 15)}
    for chunk in (1, 4):
        eng = Engine(params, cfg, batch=2, max_len=128,
                     prefill_chunk=chunk, decode_steps=1)
        prompts = [[1, 2, 3, 4, 5, 6, 7], [3, 4], [5, 6, 9, 11, 13],
                   [9, 8, 7], [2] * 11]
        arrivals = [0, 0, 1, 3, 6]
        for i, (p, a) in enumerate(zip(prompts, arrivals)):
            eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=4,
                               arrival_tick=a))
        done = eng.run()
        assert len(done) == len(prompts)
        assert (eng.tick_count, eng.dispatch_count) == anchor[chunk], \
            (chunk, eng.tick_count, eng.dispatch_count)
        for r in done:
            want = reference_generate(params, cfg, r.prompt, 4, 512)
            assert r.out_tokens == want, (chunk, r.rid, r.out_tokens, want)


@pytest.mark.parametrize("sampler", ["greedy", "temperature"])
def test_mid_megatick_finish_boundary(sampler):
    """A slot that exhausts ``max_new_tokens`` at step j < K freezes
    byte-identically for the rest of the megatick while its neighbour
    keeps decoding: per-request max_new 5 and 11 under K=8 (the first
    request finishes 5 steps into its second megatick's scan window)."""
    cfg, params = _setup()
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (6, 4)]
    max_news = [5, 11]

    def run(K):
        eng = Engine(params, cfg, batch=2, max_len=64, prefill_chunk=8,
                     sampler=sampler, seed=7, decode_steps=K)
        for i, (p, mn) in enumerate(zip(prompts, max_news)):
            eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=mn,
                               temp=1.0))
        return {r.rid: r.out_tokens for r in eng.run()}

    base, mega = run(1), run(8)
    assert {rid: len(t) for rid, t in mega.items()} == {0: 5, 1: 11}
    assert mega == base, (sampler, mega, base)


@pytest.mark.parametrize("sampler", ["greedy", "temperature"])
def test_megatick_preemption_token_identity(sampler):
    """Preemption moves to megatick boundaries: a pool too small for
    combined growth preempts a victim mid-run, and the resumed streams
    (greedy and seeded temperature) still match the single-step engine
    token for token."""
    cfg, params = _setup()
    prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8, 7, 6, 5, 4, 3]]
    base, _ = _run(params, cfg, prompts, K=1, sampler=sampler,
                   max_new=8, n_blocks=2, block_size=8)
    out, eng = _run(params, cfg, prompts, K=4, sampler=sampler,
                    max_new=8, n_blocks=2, block_size=8)
    assert eng.preempt_count >= 1
    assert out == base, (sampler, out, base)


def test_megatick_sliding_window_reclaim_token_identity():
    """Sliding-window reclaim punches -1 holes at megatick boundaries:
    blocks still reclaim under live megaticks and the stream matches
    the solo reference."""
    cfg, params = _setup()
    cfgw = cfg.replace(sliding_window=16)
    paramsw = lm.init_params(jax.random.PRNGKey(0), cfgw)
    rng = np.random.default_rng(9)
    prompt = [int(t) for t in rng.integers(1, cfgw.vocab_size, 30)]
    for K in (1, 4):
        eng = Engine(paramsw, cfgw, batch=2, max_len=64, prefill_chunk=8,
                     block_size=8, decode_steps=K)
        eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=12))
        done = eng.run()
        assert eng.pool.blocks_reclaimed >= 3, K
        want = reference_generate(paramsw, cfgw, prompt, 12, 64)
        assert done[0].out_tokens == want, (K, done[0].out_tokens, want)


def test_megatick_dispatch_accounting():
    """THE structural win: a lockstep decode workload under K=4 costs
    <= 1/K dispatches per decode token (counted from the engine's own
    counters), and the ``tokens_per_dispatch`` metric reports it."""
    cfg, params = _setup()
    rng = np.random.default_rng(4)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, 6)]
               for _ in range(2)]
    K = 4
    out, eng = _run(params, cfg, prompts, K=K, max_new=9, prefill_chunk=8)
    assert eng.decode_dispatch_count > 0
    dpt = eng.decode_dispatch_count / eng.decode_token_count
    assert dpt <= 1.0 / K, (eng.decode_dispatch_count,
                            eng.decode_token_count)
    m = eng.metrics([])
    assert m["decode_steps"] == K
    assert m["tokens_per_dispatch"] >= K
    # admission stays at megatick boundaries: a staggered workload
    # under megaticks still drains completely (covered by _run's
    # completion assert) with the same streams as single-step
    base, _ = _run(params, cfg, prompts, K=1, max_new=9,
                   prefill_chunk=8, stagger=2)
    stag, _ = _run(params, cfg, prompts, K=K, max_new=9,
                   prefill_chunk=8, stagger=2)
    assert stag == base


def test_decode_steps_validation():
    cfg, params = _setup(n_layers=1)
    with pytest.raises(ValueError, match="decode_steps"):
        Engine(params, cfg, batch=2, max_len=64, decode_steps=0)


def test_promoted_megatick_bsp_check_8_devices():
    """Per-PR promotion of the bsp-mode megatick identity check: one
    8-fake-device subprocess, greedy only — the nightly battery runs
    the full mode x sampler x window matrix
    (``check_engine_megatick_token_identity``)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("from repro.testing import distributed_checks as dc; "
            "dc.check_engine_megatick_bsp_small(); print('OK')")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "OK" in proc.stdout, \
        f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-2000:]}"
