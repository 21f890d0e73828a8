"""The benchmark's own arithmetic, on the CPU: traffic generators,
percentiles and rates, FLOP counts, trace reduction, and finding each
part of a cell by name."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import flops, harness, model, trace  # noqa: E402
from bench.measure import Run, percentile  # noqa: E402

BENCH = harness.benchmark()
LENGTHS = {"prompt": {"median": 128, "sigma": 0.8, "min": 16, "max": 1024},
           "output": {"median": 64, "sigma": 0.8, "min": 8, "max": 512}}


def _sizes(spec):
    items = (spec["requests"] if spec["mode"] == "open"
             else [r for c in spec["clients"] for r in c])
    return [(len(r["prompt"]), r["max_new"]) for r in items]


@pytest.mark.parametrize("kind_name,params", [
    ("poisson_open", {"rate": 3.0, **LENGTHS}),
    ("closed_loop", {"clients": 8, "requests_per_client": 5,
                     "layout_seed": 0, **LENGTHS}),
])
def test_generator_is_deterministic_per_seed_and_clipped(kind_name, params):
    gen = harness.kind(kind_name)
    a = gen.schedule(params, 2**31 + 17, 40.0, 32064, 16)
    b = gen.schedule(params, 2**31 + 17, 40.0, 32064, 16)
    c = gen.schedule(params, 5, 40.0, 32064, 16)
    assert a == b
    assert a != c
    # another seed sends the same prompt and output lengths; an open
    # loop sends them in another order, a closed loop in the same one
    for k in (0, 1):
        assert sorted(x[k] for x in _sizes(a)) \
            == sorted(x[k] for x in _sizes(c))
    assert (_sizes(a) == _sizes(c)) == (a["mode"] == "closed")
    for p, o in _sizes(a):
        assert 16 <= p <= 1024 and 8 <= o <= 512
    items = (a["requests"] if a["mode"] == "open"
             else [r for cl in a["clients"] for r in cl])
    assert all(1 <= t < 32064 for r in items for t in r["prompt"])
    if a["mode"] == "open":
        dues = [r["due"] for r in a["requests"]]
        assert len(dues) == 120
        assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 40.0
    else:
        assert len(a["clients"]) == 8
        assert all(len(cl) == 5 for cl in a["clients"])


def test_lognormal_draw_reads_the_median():
    from bench.traffic import sampling
    xs = sampling.lognormal(101, 128, 0.8, 16, 1024,
                            sampling.rng_for(1, "x"))
    assert sorted(xs)[50] == 128
    assert min(xs) >= 16 and max(xs) <= 1024


def test_percentile_is_numpy_linear_and_keeps_infinity():
    import numpy as np
    xs = [5.0, 1.0, 3.0, 2.0, 8.0, 13.0]
    for q in (0, 10, 50, 90, 100):
        assert percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))
    assert percentile([1.0, 2.0, math.inf], 90) == math.inf
    assert percentile([1.0, 2.0, 3.0, math.inf], 50) == 2.5


def _run(mode="open"):
    shape = model.Shape(2, 8, 2, 2, 16, 32, 10000.0, 1e-5)
    recs = [
        # due 0.5, first token at 1.0 (2 tokens), then 1 at 1.5 and 1 at 2.0
        {"due": 0.5, "sent": 10.6, "status": 200, "error": None,
         "finish": "length", "tokens": [1, 2, 3, 4],
         "events": [[11.0, 2], [11.5, 1], [12.0, 1]]},
        # refused
        {"due": 1.0, "sent": 11.0, "status": 429, "error": "full",
         "finish": None, "tokens": [], "events": []},
        # streams across the window's end at 20
        {"due": 2.0, "sent": 12.0, "status": 200, "error": None,
         "finish": None, "tokens": [5, 6, 7],
         "events": [[19.0, 1], [19.5, 1], [20.5, 1]]},
    ]
    return Run(records=recs, t0=10.0, t1=20.0, mode=mode, setup_s=3.0,
               shape=shape, chips=1, peak_flops=1e9, prompts=[3, 2, 4])


def test_latency_and_rate_arithmetic():
    run = _run()
    assert run.ttft_ms() == pytest.approx([500.0, math.inf, 7000.0])
    assert run.tpot_ms()[:2] == pytest.approx([1000.0 / 3, math.inf])
    # open loop: the whole request, drain included
    assert run.tpot_ms()[2] == pytest.approx(750.0)
    assert run.output_tokens_in_window() == 6
    assert run.lateness_s() == pytest.approx([0.1, 0.0, 0.0])
    closed = _run("closed")
    # closed loop: only what arrived inside the window
    assert closed.tpot_ms()[2] == pytest.approx(500.0)
    reader = harness.metric_reader("output_tokens_per_s")
    assert reader.read(run) == pytest.approx(0.6)
    assert harness.metric_reader("setup_s").read(run) == 3.0


def test_window_flops_count_each_token_once():
    run = _run()
    s = run.shape
    # request 0: prompt 3, first event 2 tokens -> positions 0..3, then
    # 4 and 5; request 2: prompt 4 -> positions 0..3 at 19.0, 4 at 19.5,
    # and 5 at 20.5, outside the window
    want = (flops.span_flops(s, 0, 6) + flops.span_flops(s, 0, 5))
    assert run.window_flops() == want
    assert sum(flops.token_flops(s, p) for p in range(6)) \
        == flops.span_flops(s, 0, 6)


@pytest.mark.parametrize("config,matmul,attn", [
    # phi3: 32 x (3072*3072*2 + 2*3072*3072 + 3*3072*8192) + 32064*3072
    ("phi3-mini-3.8b", 3_722_379_264, 4 * 32 * 32 * 96),
    # glm4: 40 x (2*4096*4096 + 2*4096*256 + 3*4096*13696) + 151552*4096
    ("glm4-9b-tp4", 8_778_678_272, 4 * 40 * 32 * 128),
])
def test_flops_match_a_hand_count(config, matmul, attn):
    conf = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    shape = model.Shape.from_config(conf)
    assert flops.matmul_params(shape) == matmul
    assert flops.attention_per_position(shape) == attn
    assert flops.token_flops(shape, 99) == 2 * matmul + 100 * attn


def test_trace_reduction_on_a_synthetic_trace():
    ms = 1_000_000
    devices = {
        "/device:TPU:0": [(0, 40 * ms, "fusion.3"),
                          (40 * ms, 50 * ms, "all-reduce.1"),
                          (45 * ms, 48 * ms, "fusion.4"),
                          (70 * ms, 90 * ms, "fusion.3")],
        "/device:TPU:1": [(0, 60 * ms, "fusion.3"),
                          (60 * ms, 90 * ms, "collective-permute-done.2")],
    }
    spans = [(50 * ms, 65 * ms, "bench.tick")]
    red = trace.reduce_trace(devices, spans, (0, 100 * ms))
    # busy: 70 ms on device 0, 90 ms on device 1
    assert red["busy_s"] == pytest.approx(0.080)
    assert red["idle_share"] == pytest.approx(0.2)
    # exposed collectives: 10 - 3 ms on device 0, 30 ms on device 1
    assert red["exposed_collective_share"] == pytest.approx(0.185)
    assert red["device_ops"][0] == ["fusion", pytest.approx(0.0615)]
    assert red["idle_gaps"] == [["bench.tick", pytest.approx(0.02)],
                                ["outside harness spans",
                                 pytest.approx(0.01)]]
    assert trace.reduce_trace({}, [], (0, 1))["idle_share"] is None


def test_the_trace_reads_only_the_chips_the_cell_uses():
    ms = 1_000_000
    planes = {"/device:TPU:0": [(0, 80 * ms, "fusion.1")],
              "/device:TPU:1": [], "/device:TPU:2": [], "/device:TPU:3": []}
    mine = trace.cell_planes(planes, [0])
    assert list(mine) == ["/device:TPU:0"]
    red = trace.reduce_trace(mine, [], (0, 100 * ms))
    assert red["idle_share"] == pytest.approx(0.2)
    assert red["busy_s"] == pytest.approx(0.080)
    assert trace.reduce_trace(planes, [], (0, 100 * ms))["idle_share"] \
        == pytest.approx(0.8)
    with pytest.raises(ValueError, match="TPU:4"):
        trace.cell_planes(planes, [0, 4])


def test_a_new_traffic_file_and_kind_are_found_by_name(tmp_path):
    (tmp_path / "traffic" / "kinds").mkdir(parents=True)
    (tmp_path / "traffic" / "kinds" / "fixed.py").write_text(
        "def limits(p):\n    return {'prompt': 4, 'output': 2, "
        "'min_prompt': 4, 'short_pure': True}\n\n\n"
        "def schedule(p, seed, seconds, vocab, conc):\n"
        "    return {'mode': 'open', 'requests': [{'due': 0.0, "
        "'prompt': [1] * 4, 'max_new': p['n']}]}\n")
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"kind": "fixed", "params": {"n": 3}}))
    t = harness.traffic("burst", root=tmp_path)
    gen = harness.kind(t["kind"], root=tmp_path)
    assert gen.schedule(t["params"], 1, 1.0, 8, 1)["requests"][0][
        "max_new"] == 3
    # the repository's own mixes load the same way
    for path in sorted((harness.BENCH / "traffic").glob("*.json")):
        t = harness.traffic(path.stem)
        harness.kind(t["kind"]).limits(t["params"])


def test_a_metric_split_by_cell_kind_shares_its_base_reader(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "mfu.py").write_text("def read(run):\n"
                                                 "    return 1.0\n")
    (tmp_path / "metrics" / "mfu.long.py").write_text("def read(run):\n"
                                                      "    return 2.0\n")
    read = lambda n: harness.metric_reader(n, tmp_path).read(None)  # noqa: E731
    assert read("mfu") == read("mfu.chat") == read("mfu.new-kind") == 1.0
    assert read("mfu.long") == 2.0
    with pytest.raises(FileNotFoundError):
        harness.metric_reader("nothing.chat", tmp_path)


def test_every_metric_has_a_reader_and_each_cell_reports_its_own():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
    for cell in BENCH["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(BENCH, cell["name"],
                                                       False)}
        layer = harness.cell_metrics(BENCH, cell["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert layer and all(m["moves"] in e2e for m in layer)


def test_warm_plan_reaches_the_top_bucket_from_the_cache():
    conf = harness.load_json(harness.BENCH / "configs"
                             / "phi3-mini-3.8b.json")
    eng = conf["engine"]
    plan = harness.warm_plan(eng, {"prompt": 1024, "output": 512,
                                   "min_prompt": 16, "short_pure": True},
                             32064, 3)
    seed_prompt, n = plan[0]
    # 1,536 tokens need 96 of the 100 blocks: the top bucket is 100,
    # reached from the 64 blocks below it
    assert (len(seed_prompt), n) == (64 * 16 + 1, 1)
    for prompt, n in plan[1:]:
        assert len(prompt) + n < eng["max_len"]
        full = (len(prompt) // 16) * 16
        assert prompt[:full] == seed_prompt[:full]


def _bench_run(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = _bench_run(["--workload", "phi3-mini-3.8b.chat", "--seed", "1",
                      "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_run_from_the_benchmark_files_alone_exits_nonzero(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench_run(["--workload", "phi3-mini-3.8b.chat", "--seed", "1",
                      "--seconds", "1"], tmp_path)
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_the_check_samples_the_longest_and_counts_wrong_answers():
    recs = [{"finish": "length", "error": None, "tokens": [1] * n}
            for n in (5, 300, 40, 200, 7)]
    recs.append({"finish": None, "error": "refused", "tokens": []})
    prompts = [[1] * 10] * 6
    picked = harness.sample_for_check(recs, prompts, 3, 32,
                                      min_served=250, min_requests=2)
    assert picked[0] == 1 and 5 not in picked
    assert sum(len(recs[i]["tokens"]) for i in picked) >= 250
    assert picked == harness.sample_for_check(recs, prompts, 3, 32,
                                              min_served=250,
                                              min_requests=2)
    recs[2]["tokens"][0] = 99          # outside a vocabulary of 32
    recs[4]["tokens"] = [1] * 6         # one token short
    assert harness.wrong_requests(recs, [5, 300, 40, 200, 7, 9], 32) == 2


def test_a_profiler_trace_gives_the_window_and_the_harness_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.trace"):
        with jax.profiler.TraceAnnotation("bench.tick"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    devices, spans, (lo, hi) = trace.from_profile(str(path), "bench.trace",
                                                 [])
    assert devices == {}                 # the CPU has no TPU planes
    assert [s[2] for s in spans] == ["bench.tick"]
    assert lo <= spans[0][0] < spans[0][1] <= hi
