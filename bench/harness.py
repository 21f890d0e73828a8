"""The benchmark's run: set up the served path, warm it, measure one
window of a cell's traffic, check what it served, report.

Everything a cell needs is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<name>.json``,
its traffic in ``bench/traffic/<name>.json``, the traffic's generator
kind in ``bench/traffic/kinds/<kind>.py`` and each metric's reader in
``bench/metrics/<name>.py`` (or ``<base>.py`` for ``<base>.<kind>``).
Adding a cell adds files and entries and edits none.

From the program the harness takes the system under test
(``launch.server.build_engine`` and ``launch.server.Server``, driven
over its socket), its registry configuration (checked against the
configuration file), its sharding rules (to place the weights the
harness makes) and its ``/v1/metrics`` counters.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import math
import os
import random
import sys
import tempfile
import time
from pathlib import Path

from bench import measure

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_SPAN = "bench.trace"
TICK_SPAN = "bench.tick"


# ---------------------------------------------------------------- lookup
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path (metric files have dots in their names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def config_file(bench: dict, name: str) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = BENCH) -> dict:
    return load_json(root / "traffic" / f"{name}.json")


def kind(name: str, root: Path = BENCH):
    return load_module(root / "traffic" / "kinds" / f"{name}.py")


def metric_reader(name: str, root: Path = BENCH):
    """``metrics/<name>.py``; for a name split by cell kind, such as
    ``mfu.chat``, the shared ``metrics/mfu.py`` where the cell kind has
    no reader of its own."""
    path = root / "metrics" / f"{name}.py"
    base = root / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return load_module(path if path.exists() or "." not in name else base)


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced. A metric without ``workloads`` belongs to
    every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in names
                             else [])]


def peak(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table)}")
    return table[device_kind]


def process_start() -> float:
    """This process's start on the ``time.monotonic`` clock."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - (time.clock_gettime(time.CLOCK_BOOTTIME)
                                   - started)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


class CompileLog:
    """Counts backend compiles (persistent-cache loads included) and the
    seconds they took, from JAX's own monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, seconds, **_):
        if event == self.COMPILE:
            self.n += 1
            self.seconds += seconds

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1


# ----------------------------------------------------------------- setup
def check_program_config(cfg, shape, conf: dict) -> None:
    """The program's registry entry must be the model the file
    describes: a changed width there is a different benchmark."""
    got = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
           "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
           "d_ff": cfg.d_ff, "vocab": cfg.vocab_size, "head_dim": cfg.hd,
           "rope_theta": cfg.rope_theta, "block": cfg.block,
           "act": cfg.act, "norm": cfg.norm, "tied": cfg.tie_embeddings,
           "window": cfg.sliding_window}
    want = {"n_layers": shape.n_layers, "d_model": shape.d_model,
            "n_heads": shape.n_heads, "n_kv_heads": shape.n_kv_heads,
            "d_ff": shape.d_ff, "vocab": shape.vocab,
            "head_dim": shape.head_dim, "rope_theta": shape.rope_theta,
            "block": "attn_mlp", "act": "swiglu", "norm": "rmsnorm",
            "tied": False, "window": None}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise SystemExit(f"program config {cfg.name} differs from "
                         f"{conf['name']} (program, file): {bad}")


def build(conf: dict, seed: int, devices):
    """Weights from ``seed`` made on the devices in one jitted call in
    the served dtype, and an engine over them from the program's
    ``build_engine``."""
    import jax
    import jax.numpy as jnp

    from bench import model
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding_rules import rules_for
    from repro.launch.mesh import make_host_mesh
    from repro.launch.server import build_engine, make_parser
    from repro.models import lm

    eng = conf["engine"]
    shape = model.Shape.from_config(conf)
    cfg = get_config(conf["arch"])
    if conf.get("smoke"):
        cfg = smoke_config(cfg)
    check_program_config(cfg, shape, conf)
    dtype = jnp.dtype(conf["torch_dtype"])
    if cfg.dtype != dtype:
        raise SystemExit(f"program serves {cfg.dtype}, file states {dtype}")
    cfg = cfg.replace(param_dtype=cfg.dtype)
    tp = eng["tp"]
    mesh = make_host_mesh(data=len(devices) // tp, model=tp, devices=devices)
    rules = rules_for(cfg, mesh)
    want = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    mine = jax.eval_shape(lambda: model.make_params(seed, shape, dtype))
    if (jax.tree.structure(want) != jax.tree.structure(mine)
            or jax.tree.leaves(want) != jax.tree.leaves(mine)):
        raise SystemExit("the program's parameter tree is not the layout "
                         "bench/model.py makes")
    shardings = rules.shardings(lm.param_axes(cfg), want)
    params = model.make_params(seed, shape, dtype, out_shardings=shardings)
    argv = ["--arch", conf["arch"], "--batch", str(eng["batch"]),
            "--max-len", str(eng["max_len"]),
            "--block-size", str(eng["block_size"]),
            "--decode-steps", str(eng["decode_steps"]),
            "--prefill-chunk", str(eng["prefill_chunk"]),
            "--tp", str(tp), "--fusion-mode", eng["fusion_mode"],
            "--sampler", "greedy", "--seed", "0"]
    if eng.get("kv_blocks"):
        argv += ["--kv-blocks", str(eng["kv_blocks"])]
    if conf.get("smoke"):
        argv.append("--smoke")
    engine = build_engine(make_parser().parse_args(argv), devices=devices,
                          params=params)
    return engine, shape


def pow2_buckets(cap: int) -> list[int]:
    """Every value of the engine's power-of-two bucketing up to ``cap``
    (1, 2, 4, ..., and ``cap`` itself)."""
    out, w = [], 1
    while w < cap:
        out.append(w)
        w *= 2
    return out + [cap]


def warm_plan(eng: dict, limits: dict, vocab: int, seed: int) -> list:
    """Warm-up requests, in order, that make the engine compile every
    program the cell's traffic can reach before the window opens.

    The engine picks a program by its static gather width (a power-of-
    two bucket of the longest live table row) and scan length, and
    compiles each twice: once for a tick right after the block tables
    changed (admission, a new block, a retirement: the tables are a
    fresh host upload) and once for a tick after none did (the tables
    come out of the last dispatch). A mixed tick scans M = max(K,
    prefill chunk) steps whenever every output is longer than K, which
    holds for the cells here; pure ticks scan 1 .. K.

    A first request prefills a prompt that reaches the top bucket the
    traffic's longest context reaches; that passes every bucket in
    mixed ticks of both kinds below the top, and its full blocks stay in
    the prefix cache. Then, for each bucket, one request reuses the
    cached blocks below it and runs two mixed ticks in its first block;
    and for each pure scan length k, two requests: one prefills
    ``r`` new tokens so that a block is exactly full when its prompt
    completes and then runs one pure tick of length k, which allocates
    the bucket's first block; the other starts in that block and runs a
    mixed tick and then a pure tick of length k in it. Pure ticks inside
    the first block happen only where a prompt can be shorter than a
    block, and pure ticks shorter than K only where a request can run
    alone (``short_pure``). Returns a list of (prompt, max_new)."""
    bs, K = eng["block_size"], eng["decode_steps"]
    M = max(K, eng["prefill_chunk"])
    max_blocks = -(-eng["max_len"] // bs)
    longest = min(limits["prompt"] + limits["output"], eng["max_len"] - 1)
    every = pow2_buckets(max_blocks)
    top = next(b for b in every if b >= min(-(-longest // bs), max_blocks))
    buckets = [b for b in every if b <= top]
    prev = {b: (buckets[i - 1] if i else 0) for i, b in enumerate(buckets)}
    # new tokens r that fill one block exactly with the K decode steps
    # piggybacked on the tick that completes the prompt
    r = next((r for r in range(1, bs + 1)
              if r + min(M - (r - M * ((r - 1) // M)), K) == bs), None)
    if r is None:
        raise SystemExit(f"no warm-up prompt fills a block of {bs} with "
                         f"M={M}, K={K}")
    rng = random.Random(f"{seed}:warm")
    ids = lambda n: [rng.randrange(1, vocab) for _ in range(n)]  # noqa: E731
    seed_prompt = ids(bs * prev[top] + 1)
    plan = [(seed_prompt, 1)]
    dK = min(M - 1, K)
    for b in buckets:
        # two mixed ticks in the bucket's first block: the second comes
        # after no table change
        plan.append((seed_prompt[:bs * prev[b]] + ids(r), 1 + K))
        for k in pow2_buckets(K) if limits["short_pure"] else [K]:
            if b > 1:
                # the pure tick that completes this request needs a new
                # block: a fresh table upload
                plan.append((seed_prompt[:bs * (prev[b] - 1)] + ids(r),
                             1 + K + k))
            if b > 1 or limits["min_prompt"] < bs:
                # one new token in the bucket's first block, a mixed tick
                # and then a pure tick in the same block
                plan.append((seed_prompt[:bs * prev[b]] + ids(1),
                             1 + dK + k))
    return plan


# ---------------------------------------------------------------- window
async def _get_json(host: str, port: int, path: str) -> dict:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: localhost\r\n"
                     f"Content-Length: 0\r\n\r\n".encode())
        await writer.drain()
        raw = await reader.read()
    finally:
        writer.close()
    return json.loads(raw.split(b"\r\n\r\n", 1)[1])


@dataclasses.dataclass
class Window:
    result: dict                # the load generator's output
    counters0: dict
    counters1: dict
    compiles_in_window: int
    compile_s_in_window: float
    trace: dict | None
    warm: dict


async def _warm(server, plan) -> dict:
    from bench import loadgen
    t = time.monotonic()
    bad = []
    for prompt, n in plan:
        rec = loadgen._record(0, 0, 0.0)
        await loadgen.complete(server.host, server.port, prompt, n, rec)
        if rec["error"] or len(rec["tokens"]) != n:
            bad.append((len(prompt), n, rec["error"], len(rec["tokens"])))
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:5]}")
    return {"requests": len(plan), "seconds": time.monotonic() - t}


async def _window(engine, spec: dict, plan, compiles, seconds: float,
                  trace_dir: str | None, drain_s: float,
                  device_ids=()) -> Window:
    from repro.launch.server import Server

    server = Server(engine, port=0, max_queue=spec.pop("max_queue"))
    await server.start()
    try:
        warm = await _warm(server, plan)
        spec.update(host=server.host, port=server.port, seconds=seconds,
                    drain_s=drain_s)
        proc = await asyncio.create_subprocess_exec(
            sys.executable, str(BENCH / "loadgen.py"),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE)
        t0 = spec["t0"] = time.monotonic() + 1.0
        comm = asyncio.create_task(proc.communicate(json.dumps(spec).encode()))
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        c0 = await _get_json(server.host, server.port, "/v1/metrics")
        n0, s0 = compiles.n, compiles.seconds
        red = None
        if trace_dir is not None:
            red = await _trace(trace_dir, seconds, device_ids)
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        c1 = await _get_json(server.host, server.port, "/v1/metrics")
        n1, s1 = compiles.n, compiles.seconds
        out, _ = await asyncio.wait_for(comm, timeout=seconds + drain_s + 120)
        if proc.returncode != 0:
            raise RuntimeError(f"load generator exited {proc.returncode}")
        result = json.loads(out)
    finally:
        await server.stop()
    return Window(result=result, counters0=c0, counters1=c1,
                  compiles_in_window=n1 - n0,
                  compile_s_in_window=s1 - s0, trace=red, warm=warm)


async def _trace(trace_dir: str, seconds: float, device_ids) -> dict:
    """Trace a few seconds from the middle of the window and reduce it;
    the trace files are deleted."""
    import jax

    from bench import trace as trace_lib

    start = min(0.3 * seconds, 10.0)
    length = min(4.0, 0.4 * seconds)
    await asyncio.sleep(start)
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(TRACE_SPAN):
            await asyncio.sleep(length)
    finally:
        jax.profiler.stop_trace()
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"profiler wrote no trace under {trace_dir}")
    devices, spans, window = trace_lib.from_profile(str(paths[-1]),
                                                    TRACE_SPAN, device_ids)
    return trace_lib.reduce_trace(devices, spans, window)


def _annotate_ticks(engine) -> None:
    """Wrap this engine instance's ``tick`` in a host span, so that the
    trace's idle gaps can be named by what the host was doing."""
    import jax

    tick = engine.tick

    def traced_tick():
        with jax.profiler.TraceAnnotation(TICK_SPAN):
            return tick()

    engine.tick = traced_tick


# ----------------------------------------------------------- correctness
def sample_for_check(records, prompts, seed: int, vocab: int,
                     min_served: int = 512, min_requests: int = 4,
                     max_tokens: int = 8192):
    """The finished requests whose served tokens the reference checks:
    the longest, then others drawn from the seed until at least
    ``min_served`` served tokens and ``min_requests`` requests are in,
    or the packed row would pass ``max_tokens``."""
    done = [i for i, r in enumerate(records)
            if r["finish"] == "length" and r["error"] is None
            and r["tokens"]]
    if not done:
        return []
    done.sort(key=lambda i: (-len(records[i]["tokens"]), i))
    rest = done[1:]
    random.Random(f"{seed}:check").shuffle(rest)
    picked = [done[0]]
    size = len(prompts[done[0]]) + len(records[done[0]]["tokens"])
    served = len(records[done[0]]["tokens"])
    for i in rest:
        if served >= min_served and len(picked) >= min_requests:
            break
        n = len(prompts[i]) + len(records[i]["tokens"])
        if size + n > max_tokens:
            continue
        picked.append(i)
        size += n
        served += len(records[i]["tokens"])
    return picked


def wrong_requests(records, max_news, vocab: int) -> int:
    """Requests that finished but say the wrong thing: another count of
    tokens than asked for, or an id outside the vocabulary."""
    bad = 0
    for r, n in zip(records, max_news):
        if r["finish"] is None:
            continue
        if (r["finish"] != "length" or len(r["tokens"]) != n
                or not all(0 <= t < vocab for t in r["tokens"])):
            bad += 1
    return bad


def gap_stats(gaps) -> dict:
    """The widest gap, and beside it the 99th percentile, the mean and
    the share of positions whose token is not the reference's best."""
    import numpy as np
    return {"gap_max": float(gaps.max()),
            "gap_p99": float(np.percentile(gaps, 99)),
            "gap_mean": float(gaps.mean()),
            "mismatch_share": float((gaps > 0).mean())}


def check_served(shape, seed: int, prompts, outputs, dtype, device=None,
                 control: bool = False) -> dict:
    """The reference over each prompt with its served tokens: the widest
    gap by which a served token's logit lies below the reference's best
    (``gap_max``); with ``control``, the same for the token the float8
    control puts first at each position (``control`` holds its
    :func:`gap_stats`)."""
    from bench import model

    ref = model.Reference(shape, seed, device=device, dtype=dtype)
    ctl = (model.Reference(shape, seed, device=device, dtype=dtype,
                           fp8=True) if control else None)
    gaps, cgaps = model.served_gaps(ref, prompts, outputs, control=ctl)
    out = {**gap_stats(gaps), "served": int(len(gaps))}
    if cgaps is not None:
        out["control"] = gap_stats(cgaps)
    return out


# -------------------------------------------------------------------- run
def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             conf: dict | None = None, traffic_conf: dict | None = None,
             devices=None, require_chip: bool = True, control: bool = False,
             drain_s: float = 60.0, t_start: float | None = None,
             warm: bool = True,
             bench: dict | None = None, log=print) -> dict:
    """One run of one cell. Returns the result line's object; with
    ``control``, also the float8 control's verdict by the same checks
    and both sides' :func:`gap_stats` under ``control``.

    ``conf``/``traffic_conf`` replace the cell's files (tests run a
    smoke-sized model on the CPU that way); ``require_chip=False`` skips
    the look for a TPU; ``warm=False`` sends no warm-up requests, so that
    programs compile or load from the cache inside the window (for the
    control's readings, which time nothing)."""
    import jax
    import jax.numpy as jnp

    t_start = process_start() if t_start is None else t_start
    bench = benchmark() if bench is None else bench
    cell = find_cell(bench, cell_name)
    conf = conf or load_json(config_file(bench, cell["config"]))
    tconf = traffic_conf or traffic(cell["traffic"])
    all_dev = jax.devices()
    if require_chip:
        if all_dev[0].platform != "tpu":
            raise SystemExit(f"needs a TPU; JAX found {all_dev[0].platform!r}")
        if len(all_dev) < cell["chips"]:
            raise SystemExit(f"{cell_name} needs {cell['chips']} chips; JAX "
                             f"found {len(all_dev)}")
    devices = list(devices or all_dev[:cell["chips"]])
    compiles = CompileLog()
    gen = kind(tconf["kind"])
    engine, shape = build(conf, seed, devices)
    eng = conf["engine"]
    spec = gen.schedule(tconf["params"], seed, seconds, shape.vocab,
                        eng["batch"])
    if spec["mode"] == "open":
        items = spec["requests"]
    else:
        items = [r for c in spec["clients"] for r in c]
    for r in items:
        if len(r["prompt"]) + r["max_new"] >= eng["max_len"]:
            raise SystemExit(f"traffic sends a context of "
                             f"{len(r['prompt']) + r['max_new']} tokens; "
                             f"max_len is {eng['max_len']}")
    plan = (warm_plan(eng, gen.limits(tconf["params"]), shape.vocab, seed)
            if warm else [])
    if trace:
        _annotate_ticks(engine)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    spec["max_queue"] = eng.get("max_queue", 64)
    try:
        win = asyncio.run(_window(engine, spec, plan, compiles, seconds,
                                  trace_dir, drain_s,
                                  [d.id for d in devices]))
    finally:
        if trace_dir is not None:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    res = win.result
    recs = res["requests"]
    if spec["mode"] == "open":
        sent = spec["requests"]
    else:
        sent = [spec["clients"][r["client"]][r["index"]]
                if r["index"] < len(spec["clients"][r["client"]])
                else {"prompt": [], "max_new": 0} for r in recs]
    prompts = [s["prompt"] for s in sent]
    kind_name = devices[0].device_kind
    pk = peak(kind_name) if require_chip else {"bf16_flops_per_s": math.nan}
    run = _measurements(res, spec["mode"], t_start, shape, len(devices),
                        pk["bf16_flops_per_s"], prompts, win)
    late = run.lateness_s()
    log(f"[bench] {cell_name} seed {seed}: warm-up {win.warm['requests']} "
        f"requests in {win.warm['seconds']:.3f} s; setup {run.setup_s:.3f} s; "
        f"compiles before the window {compiles.n - win.compiles_in_window} "
        f"({compiles.cache_hits} cache hits), in the window "
        f"{win.compiles_in_window} ({win.compile_s_in_window:.3f} s); "
        f"generator late p50 {_pct(late, 50):.4f} s max "
        f"{max(late, default=0.0):.4f} s; memory peak {mem_peak}",
        file=sys.stderr)
    metrics = {}
    for m in cell_metrics(bench, cell_name, trace):
        v = metric_reader(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = len(recs)
    failed = sum(map(measure.failed, recs))
    never = sum(1 for r in recs
                if r["error"] == "not answered within the drain")
    wrong = wrong_requests(recs, [s["max_new"] for s in sent], shape.vocab)
    picked = sample_for_check(recs, prompts, seed, shape.vocab)
    # the program's state goes before the reference runs: its peak is
    # read, and the reference never shares the chip with it
    del engine
    gc.collect()
    checks = {}
    if picked:
        t = time.monotonic()
        got = check_served(shape, seed, [prompts[i] for i in picked],
                           [recs[i]["tokens"] for i in picked],
                           jnp.dtype(conf["torch_dtype"]), devices[0],
                           control=control)
        log(f"[bench] reference over {len(picked)} requests, "
            f"{got['served']} served tokens, took "
            f"{time.monotonic() - t:.3f} s", file=sys.stderr)
        checks["gap_max"] = {"value": got["gap_max"],
                             "limit": conf["checks"]["gap_max"]}
    checks["wrong_requests"] = {"value": wrong, "limit": 0}
    checks["never_answered"] = {"value": never, "limit": 0}
    correct = verdict(checks)
    control_correct = None
    if control and picked:
        # the control put in the program's place: the same checks with
        # the float8 forward's tokens in place of the served ones
        control_correct = verdict({**checks, "gap_max": {
            "value": got["control"]["gap_max"],
            "limit": conf["checks"]["gap_max"]}})
    device = {"platform": devices[0].platform, "kind": kind_name,
              "count": jax.device_count(), "memory_peak_bytes": mem_peak}
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if trace and win.trace is not None:
        device["busy_s"] = win.trace["busy_s"]
        device["window_s"] = win.trace["window_s"]
        line["breakdown"] = {"device_ops": win.trace["device_ops"],
                             "idle_gaps": win.trace["idle_gaps"]}
    if control:
        line["control"] = {"correct": control_correct,
                           "program": got if picked else None}
    line["checks"] = checks
    return line


def verdict(checks: dict) -> bool:
    """``correct``: every compared number within its limit, and the
    served tokens compared at all."""
    return ("gap_max" in checks
            and all(c["value"] <= c["limit"] for c in checks.values()))


def _pct(xs, q):
    return measure.percentile(xs, q) if xs else 0.0


def _measurements(res, mode, t_start, shape, chips, peak_flops, prompts,
                  win: Window):
    return measure.Run(records=res["requests"], t0=res["t0"], t1=res["t1"],
               mode=mode, setup_s=res["t0"] - t_start, shape=shape,
               chips=chips, peak_flops=peak_flops,
               prompts=[len(p) for p in prompts], counters0=win.counters0,
               counters1=win.counters1, trace=win.trace)


def print_result(line: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the result as the last line of standard
    output."""
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
