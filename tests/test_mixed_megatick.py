"""Mixed prefill+decode megaticks: per-PR (fast tier) coverage.

``Engine(decode_steps=K)`` no longer bails out to one-dispatch-per-token
when a slot is prefilling: a batch with prefill in flight runs ONE fused
jitted program (``lm.decode_mixed``) in which each slot carries a
per-step role — consume the next prompt token, or sample-and-feed-back —
with sampling device-resident. The contract under test:

* mid-megatick prefill->decode transitions are TOKEN-identical to the
  single-step engine for greedy AND the seeded temperature sampler: a
  slot that consumes its last prompt token at step j samples its first
  output token at step j, in the same dispatch, not next tick;
* identity holds through preemption at megatick boundaries and
  sliding-window reclaim;
* under a staggered-arrival workload (prefill always in flight — the
  case the pure-decode counters cannot see), the COMBINED
  dispatches-per-decode-token stays <= 1/K, counted from the engine's
  structural counters;
* ``megatick_token_budget`` caps the per-slot prompt+decode quota and
  must be >= ``decode_steps``;
* one tiny 8-fake-device subprocess promotes the bsp-mode battery
  check (``check_engine_mixed_megatick_bsp_small``) into the per-PR
  tier.

``decode_steps=1`` byte-identity stays pinned by
``test_decode_multi.py::test_decode_steps_one_is_byte_identical_anchor``.
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


def _setup(n_layers=2):
    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=n_layers)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run(params, cfg, prompts, *, K, sampler="greedy", max_new=9,
         n_blocks=None, batch=4, max_len=64, prefill_chunk=4,
         block_size=16, stagger=2, budget=None):
    """Staggered-arrival harness: with ``stagger > 0`` new prompts keep
    arriving while earlier slots decode, so a K>1 engine runs the MIXED
    program for most of its dispatches."""
    eng = Engine(params, cfg, batch=batch, max_len=max_len,
                 prefill_chunk=prefill_chunk, sampler=sampler, seed=7,
                 block_size=block_size, n_blocks=n_blocks,
                 decode_steps=K, megatick_token_budget=budget)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=max_new,
                           temp=1.0), at_tick=i * stagger)
    done = eng.run()
    assert len(done) == len(prompts), (K, sampler, len(done))
    return {r.rid: r.out_tokens for r in done}, eng


@pytest.mark.parametrize("sampler", ["greedy", "temperature"])
def test_mixed_megatick_token_identity_vs_single_step(sampler):
    """Staggered arrivals under K in {2, 4}: the mixed-megatick engine's
    streams are token-identical to the single-step engine's for both
    samplers, the mixed program actually engaged (mixed dispatches and
    prompt tokens counted), and total dispatches strictly shrink."""
    cfg, params = _setup()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (7, 3, 11, 5)]
    base, eng1 = _run(params, cfg, prompts, K=1, sampler=sampler)
    assert eng1.mixed_dispatch_count == 0      # K=1 never fuses
    for K in (2, 4):
        out, engK = _run(params, cfg, prompts, K=K, sampler=sampler)
        assert out == base, (K, sampler, out, base)
        assert engK.mixed_dispatch_count > 0, (K, sampler)
        assert engK.mixed_prompt_token_count > 0, (K, sampler)
        assert engK.mixed_decode_token_count > 0, (K, sampler)
        assert engK.dispatch_count < eng1.dispatch_count, (K, sampler)


def test_first_token_sampled_in_completing_dispatch():
    """The transition contract, structurally: a slot that consumes its
    last prompt token at step j samples its first output token at step
    j — so ONE mixed megatick with quota M=8 both finishes a 5-token
    prompt and emits 4 tokens (1 at the completing step + 3
    piggybacked decode steps, K=4)."""
    cfg, params = _setup()
    rng = np.random.default_rng(1)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 5)]
    eng = Engine(params, cfg, batch=2, max_len=64, prefill_chunk=8,
                 decode_steps=4, megatick_token_budget=8)
    eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=9))
    eng.tick()
    req = next(iter(eng.active.values()))
    assert req.consumed == 5                   # prompt fully consumed
    assert len(req.out_tokens) == 4, req.out_tokens
    assert eng.mixed_dispatch_count == 1
    assert eng.mixed_prompt_token_count == 5
    assert eng.mixed_decode_token_count == 4
    assert req.first_token_t is not None       # TTFT stamped this tick


@pytest.mark.parametrize("budget", [4, 6, 16])
def test_megatick_token_budget_quota(budget):
    """``megatick_token_budget`` reshapes the prefill/decode split
    (smaller M = more mixed dispatches to drain the same prompt) but
    never the tokens: streams stay identical to the single-step engine
    across quotas."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (9, 4, 13)]
    base, _ = _run(params, cfg, prompts, K=1)
    out, eng = _run(params, cfg, prompts, K=4, budget=budget)
    assert eng.megatick_tokens == budget
    assert eng.mixed_dispatch_count > 0, budget
    assert out == base, (budget, out, base)


def test_megatick_token_budget_validation():
    cfg, params = _setup(n_layers=1)
    with pytest.raises(ValueError, match="megatick_token_budget"):
        Engine(params, cfg, batch=2, max_len=64, decode_steps=4,
               megatick_token_budget=3)
    # default quota covers both a full decode megatick and a full
    # prefill chunk
    eng = Engine(params, cfg, batch=2, max_len=64, prefill_chunk=8,
                 decode_steps=4)
    assert eng.megatick_tokens == 8


@pytest.mark.parametrize("sampler", ["greedy", "temperature"])
def test_mixed_megatick_preemption_token_identity(sampler):
    """A pool too small for combined growth preempts mid-run while
    prompts are still arriving; the resumed streams (greedy and seeded
    temperature) still match the single-step engine token for token."""
    cfg, params = _setup()
    prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8, 7, 6, 5, 4, 3],
               [2, 4, 6, 8, 10]]
    base, _ = _run(params, cfg, prompts, K=1, sampler=sampler,
                   max_new=8, batch=2, n_blocks=2, block_size=8)
    out, eng = _run(params, cfg, prompts, K=4, sampler=sampler,
                    max_new=8, batch=2, n_blocks=2, block_size=8)
    assert eng.preempt_count >= 1
    assert eng.mixed_dispatch_count > 0
    assert out == base, (sampler, out, base)


def test_mixed_megatick_sliding_window_reclaim_token_identity():
    """Sliding-window reclaim punches -1 holes at mixed-megatick
    boundaries (a 30-token prompt spends several megaticks prefilling,
    then transitions to decode mid-dispatch) with streams identical to
    the single-step engine."""
    cfg, params = _setup()
    cfgw = cfg.replace(sliding_window=16)
    paramsw = lm.init_params(jax.random.PRNGKey(0), cfgw)
    rng = np.random.default_rng(9)
    prompt = [int(t) for t in rng.integers(1, cfgw.vocab_size, 30)]
    streams = {}
    for K in (1, 4):
        eng = Engine(paramsw, cfgw, batch=2, max_len=64, prefill_chunk=8,
                     block_size=8, decode_steps=K)
        eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=12))
        done = eng.run()
        assert eng.pool.blocks_reclaimed >= 3, K
        if K > 1:
            assert eng.mixed_dispatch_count > 0
        streams[K] = done[0].out_tokens
    assert streams[1] == streams[4], streams


def test_mixed_megatick_dispatch_accounting():
    """THE structural win under continuous arrivals: staggered prompts
    keep prefill in flight (the pure-decode fast path alone cannot
    engage), yet the COMBINED decode dispatches-per-token — pure +
    mixed dispatches over all decode tokens — stays <= 1/K, and the
    metrics surface the mixed counters."""
    cfg, params = _setup()
    rng = np.random.default_rng(4)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, 6)]
               for _ in range(4)]
    K = 4
    out, eng = _run(params, cfg, prompts, K=K, max_new=16,
                    prefill_chunk=8, stagger=2)
    assert eng.mixed_dispatch_count > 0
    dispatches = eng.decode_dispatch_count + eng.mixed_dispatch_count
    tokens = eng.decode_token_count + eng.mixed_decode_token_count
    assert tokens == 4 * 16
    dpt = dispatches / tokens
    assert dpt <= 1.0 / K, (dispatches, tokens)
    m = eng.metrics([])
    assert m["mixed_dispatches"] == eng.mixed_dispatch_count
    assert m["mixed_prompt_tokens"] == eng.mixed_prompt_token_count
    assert m["mixed_decode_tokens"] == eng.mixed_decode_token_count
    assert m["decode_dispatches_per_token"] == round(dpt, 4)
    assert m["decode_dispatches_per_token"] <= 1.0 / K


def test_promoted_mixed_megatick_bsp_check_8_devices():
    """Per-PR promotion of the bsp-mode mixed-megatick identity check:
    one 8-fake-device subprocess, greedy only — the nightly battery
    runs the full mode x sampler x window matrix
    (``check_engine_mixed_megatick_token_identity``)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("from repro.testing import distributed_checks as dc; "
            "dc.check_engine_mixed_megatick_bsp_small(); print('OK')")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "OK" in proc.stdout, \
        f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-2000:]}"
