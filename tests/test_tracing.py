"""Spans and counters inside the served path, on a tiny CPU engine.

The engine's ticks are ``engine.*`` host spans whose seconds accumulate
in ``engine_<phase>_s``; ``queue_wait_s``/``admissions`` time arrival to
first admission; ``scan_slot_steps``/``scan_slot_capacity`` count the
slot-steps the dispatched scans did work in against scan length x
batch. The server adds ``server.*`` spans and the gap between
back-to-back ticks, and ``GET /v1/metrics`` computes its latency
summaries on request. The jitted programs carry stable names.
"""
import asyncio
import functools
import glob
import re
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.launch.server import SERVER_SPANS, Server
from repro.models import lm
from repro.serving import client as cl
from repro.serving.engine import TICK_SPANS, Engine, Request
from repro.serving.metrics import Spans, counter_name

ROOT = Path(__file__).resolve().parents[1]


@functools.lru_cache(maxsize=1)
def _setup():
    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=1)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(batch=2, **kw):
    cfg, params = _setup()
    kw.setdefault("decode_steps", 4)
    kw.setdefault("block_size", 16)
    kw.setdefault("n_blocks", 12)
    kw.setdefault("prefill_chunk", 8)
    return Engine(params, cfg, batch=batch, max_len=64, **kw)


def _prompt(n, start=1):
    return [start + i for i in range(n)]


# ------------------------------------------------------------- the helper
def test_span_counts_seconds_and_nests():
    spans = Spans("a.b")
    assert spans.seconds == {"a_b_s": 0.0}
    with spans.span("a.b", tick=1) as outer:
        with spans.span("a.c") as inner:
            inner.set_metadata(rids="1 2")
            time.sleep(0.01)
    assert inner.seconds >= 0.01
    assert outer.seconds >= inner.seconds
    assert spans.seconds["a_b_s"] == outer.seconds
    assert spans.seconds["a_c_s"] == inner.seconds
    assert counter_name("engine.readback") == "engine_readback_s"


def test_span_counts_a_raising_body():
    spans = Spans()
    with pytest.raises(ValueError):
        with spans.span("x.y"):
            raise ValueError("boom")
    assert spans.seconds["x_y_s"] >= 0.0


# ------------------------------------------------------ (a) scan fill
def test_scan_fill_matches_hand_count_mixed_then_pure():
    """One request, prompt 10, 6 new tokens, batch 2, K=4, M=8:
    tick 1 mixed: 8 prompt steps, S=8 -> 8 of 16 slot-steps;
    tick 2 mixed: 2 prompt + 4 decode steps, S=8 -> 6 of 16;
    tick 3 pure: 1 decode step (5 of 6 tokens out), K bucket 1 -> 1 of 2."""
    eng = _engine()
    req = Request(rid=0, prompt=_prompt(10), max_new_tokens=6)
    eng.submit(req)
    eng.tick()
    assert (eng.scan_slot_steps, eng.scan_slot_capacity) == (8, 16)
    eng.tick()
    assert (eng.scan_slot_steps, eng.scan_slot_capacity) == (14, 32)
    assert len(req.out_tokens) == 5 and eng.mixed_dispatch_count == 2
    done = eng.tick()
    assert done == [req] and eng.decode_dispatch_count == 1
    assert (eng.scan_slot_steps, eng.scan_slot_capacity) == (15, 34)
    m = eng.metrics(done)
    assert (m["scan_slot_steps"], m["scan_slot_capacity"]) == (15, 34)


def test_scan_fill_single_step_path():
    """decode_steps=1: a chunk tick counts its slots' token counts
    against the chunk width it compiled, a one-token tick against 1."""
    eng = _engine(decode_steps=1, prefill_chunk=4)
    req = Request(rid=0, prompt=_prompt(3), max_new_tokens=2)
    eng.submit(req)
    eng.tick()                      # 3 prompt tokens, chunk bucket 4
    assert (eng.scan_slot_steps, eng.scan_slot_capacity) == (3, 8)
    eng.tick()                      # one decode token, width 1
    assert (eng.scan_slot_steps, eng.scan_slot_capacity) == (4, 10)


# ----------------------------------------- (b) queue wait, once per request
@pytest.mark.parametrize("K", [1, 4])
def test_queue_wait_counts_each_request_once_across_preemption(K):
    cfg, params = _setup()
    eng = Engine(params, cfg, batch=2, max_len=64, prefill_chunk=4,
                 block_size=8, n_blocks=2, decode_steps=K)
    admitted = []
    admit = eng._admit

    def counting_admit():
        got = admit()
        admitted.extend(r.rid for r in got)
        return got

    eng._admit = counting_admit
    t0 = time.monotonic()
    reqs = [Request(rid=0, prompt=_prompt(7), max_new_tokens=8),
            Request(rid=1, prompt=_prompt(7, 9), max_new_tokens=8)]
    for r in reqs:
        # arrived 100 s ago: a second count of either would add 100 s
        r.arrived_t = t0 - 100.0
        eng.submit(r)
    assert all(r.arrived_t == t0 - 100.0 for r in reqs)
    done = eng.run()
    elapsed = time.monotonic() - t0
    assert len(done) == 2 and eng.preempt_count >= 1
    assert len(admitted) > 2            # the victim came back
    assert eng.admission_count == 2
    assert 200.0 <= eng.queue_wait_s <= 200.0 + 2 * elapsed
    m = eng.metrics(done)
    assert m["admissions"] == 2 and m["queue_wait_s"] == eng.queue_wait_s


def test_submit_stamps_arrival_when_the_server_did_not():
    eng = _engine()
    before = time.monotonic()
    req = Request(rid=0, prompt=_prompt(3), max_new_tokens=2)
    eng.submit(req)
    assert before <= req.arrived_t <= time.monotonic()


# --------------------------------------------- (c) spans within the tick
def test_engine_span_seconds_sum_within_tick_seconds():
    eng = _engine()
    for i in range(3):
        eng.submit(Request(rid=i, prompt=_prompt(5 + 7 * i),
                           max_new_tokens=6 + i))
    done = eng.run()
    assert len(done) == 3
    m = eng.metrics(done)
    names = [counter_name(s) for s in TICK_SPANS]
    assert all(n in m for n in names)
    assert m["engine_tick_s"] > 0 and m["engine_readback_s"] > 0
    assert sum(m[n] for n in names[1:]) <= m["engine_tick_s"]
    # the watchdog is fed the tick span's duration
    assert len(eng.watchdog._times) == eng.tick_count


def test_straggler_watchdog_records_span_seconds():
    """The watchdog reads no clock of its own around a tick: each
    duration it records is one ``engine.tick`` span's monotonic
    seconds, so they sum to the ``engine_tick_s`` counter."""
    eng = _engine()
    eng.submit(Request(rid=0, prompt=_prompt(9), max_new_tokens=5))
    eng.run()
    times = list(eng.watchdog._times)
    assert 0 < len(times) == eng.tick_count < eng.watchdog.window
    assert all(t >= 0.0 for t in times)
    assert sum(times) == pytest.approx(eng.spans.seconds["engine_tick_s"])


# -------------------------------------------------- (d) named programs
def test_jitted_programs_carry_their_names():
    eng = _engine()
    assert eng._stepM.__name__ == "megatick_mixed"
    assert eng._stepK.__name__ == "megatick_decode"
    assert eng._step1.__name__ == "step_single"
    assert eng._stepC.__name__ == "step_chunk"
    assert eng.pool._copy_fn.__name__ == "pool_copy_block"
    B, pool = eng.batch, eng.pool
    z = np.zeros((B,), np.int32)
    low = eng._stepK.lower(eng.params, np.zeros((B, 1), np.int32),
                           np.ones((B,), np.int32), pool.state, z, z,
                           np.ones((B,), np.float32), z, 2, 1)
    text = low.as_text(debug_info=True)
    assert "jit_megatick_decode" in text
    for scope in ("embed", "attention", "kv_write", "mlp", "lm_head",
                  "sample"):
        assert re.search(rf'[/"]{scope}/', text), scope
    low = eng._stepM.lower(eng.params, np.zeros((B, 2), np.int32),
                           np.zeros((B, 1), np.int32), z, z, z, pool.state,
                           z, z, np.ones((B,), np.float32), z, 2, 1)
    assert "jit_megatick_mixed" in low.as_text()
    low = eng._step1.lower(eng.params, np.zeros((B, 1), np.int32),
                           np.ones((B,), bool), pool.state, 1)
    assert "jit_step_single" in low.as_text()
    low = eng._stepC.lower(eng.params, np.zeros((B, 2), np.int32), z,
                           pool.state, 1)
    assert "jit_step_chunk" in low.as_text()
    low = pool._copy_fn.lower(pool.state, 0, 1)
    assert "jit_pool_copy_block" in low.as_text()


# ------------------------------------ (e) the spans in a profiler trace
def test_profiler_trace_holds_engine_spans_in_order(tmp_path):
    sys.path.insert(0, str(ROOT))
    from bench import trace as trace_lib

    eng = _engine()
    eng.submit(Request(rid=0, prompt=_prompt(5), max_new_tokens=9))
    eng.tick()                      # compiles outside the trace
    eng.submit(Request(rid=7, prompt=_prompt(5, 20), max_new_tokens=9))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.tick()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))
    devices, spans, (lo, hi) = trace_lib.from_profile(
        path[-1], "engine.tick", (), host_prefix="engine.")
    assert devices == {}
    inside = sorted(s for s in spans if lo <= s[0] and s[1] <= hi)
    assert [n for _, _, n in inside] == list(TICK_SPANS[1:])
    for (_, e, _), (s, _, _) in zip(inside, inside[1:]):
        assert e <= s


# ----------------------------------------------- (f) the metrics endpoint
def test_metrics_endpoint_has_the_new_keys_and_engine_latencies():
    eng = _engine()
    finished = []
    tick = eng.tick

    def recording_tick():
        got = tick()
        finished.extend(got)
        return got

    eng.tick = recording_tick

    async def run():
        srv = Server(eng, port=0)
        await srv.start()
        try:
            await asyncio.gather(*(
                cl.complete(srv.host, srv.port, _prompt(3 + 4 * i, 1 + i),
                            max_new_tokens=5 + 2 * i)
                for i in range(3)))
            return await cl.metrics(srv.host, srv.port)
        finally:
            await srv.stop()

    m = asyncio.run(run())
    assert len(finished) == 3
    want = eng.metrics(finished)
    for key in ("requests", "new_tokens", "p50_ttft_s", "p99_ttft_s",
                "max_ttft_s", "p50_tpot_s", "p99_tpot_s", "max_tpot_s"):
        assert m[key] == want[key], key
    assert m["requests"] == 3
    new = ["queue_wait_s", "admissions", "scan_slot_steps",
           "scan_slot_capacity", "server_gap_s", "server_gaps"]
    new += [counter_name(s) for s in TICK_SPANS + SERVER_SPANS]
    for key in new:
        assert key in m, key
    assert m["admissions"] == 3
    assert 0 < m["scan_slot_steps"] <= m["scan_slot_capacity"]
    assert m["server_gaps"] >= 1 and m["server_gap_s"] > 0
    assert m["server_intake_s"] > 0 and m["server_flush_s"] > 0


def test_flush_keeps_latency_pairs_not_requests():
    """The server keeps each finished request's (TTFT, TPOT), and its
    per-tick snapshot is counters only."""
    eng = _engine()

    async def run():
        srv = Server(eng, port=0)
        await srv.start()
        try:
            await cl.complete(srv.host, srv.port, _prompt(4),
                              max_new_tokens=3)
        finally:
            await srv.stop()
        return srv

    srv = asyncio.run(run())
    assert len(srv._done) == 1
    ttft, tpot = srv._done[0]
    assert isinstance(ttft, float) and isinstance(tpot, float)
    assert not any(k.startswith(("p50_", "p99_", "max_"))
                   for k in srv._metrics)
    assert srv._metrics["new_tokens"] == 3
