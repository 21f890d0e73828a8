"""Sweep an open-loop cell's arrival rate on the chip, in one process, to
find the knee: the highest rate the served path sustains.

    python3 bench/sweep.py --workload phi3-mini-3.8b.chat --seed 7 \
        --seconds 40 --rates 1,2,3,4 [--set-rate]

Sets the cell up once, then measures one window per rate (each drained
before the next) and prints one JSON line per rate: requests offered,
finished within the window, still unfinished at its end, output tokens
per second in the window, the TTFT and TPOT percentiles, and the median
TTFT of the requests due in the window's first and last thirds. Past
the knee the queue grows all through the window, and so does TTFT.

The knee is the highest swept rate up to which every rate is sustained
(:func:`sustained`). The last line gives it; with ``--set-rate`` the
cell's traffic file gets 0.8 x the knee as its rate.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--set-rate", action="store_true")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1

    bench = harness.benchmark()
    cell = harness.find_cell(bench, args.workload)
    conf = harness.load_json(harness.config_file(bench, cell["config"]))
    tconf = harness.traffic(cell["traffic"])
    gen = harness.kind(tconf["kind"])
    compiles = harness.CompileLog()
    engine, shape = harness.build(conf, args.seed,
                                  jax.devices()[:cell["chips"]])
    eng = conf["engine"]
    plan = harness.warm_plan(eng, gen.limits(tconf["params"]), shape.vocab,
                             args.seed)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        params = {**tconf["params"], "rate": rate}
        spec = gen.schedule(params, args.seed + i, args.seconds,
                            shape.vocab, eng["batch"])
        spec["max_queue"] = eng.get("max_queue", 64)
        win = asyncio.run(harness._window(
            engine, spec, plan if i == 0 else [], compiles, args.seconds,
            None, 120.0))
        rows.append(summary(rate, win, spec, shape, cell["chips"],
                            args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    knee = knee_of(rows)
    out = {"knee": knee, "rate": None if knee is None else round(0.8 * knee, 2)}
    if args.set_rate and knee is not None:
        path = harness.BENCH / "traffic" / f"{cell['traffic']}.json"
        tconf["params"]["rate"] = out["rate"]
        with open(path, "w") as f:
            json.dump(tconf, f, indent=2)
            f.write("\n")
        out["written"] = str(path.relative_to(harness.ROOT))
    print(json.dumps(out), flush=True)
    return 0


def sustained(row: dict, base: dict) -> bool:
    """A rate is sustained when no request failed and its median TTFT
    and TPOT stay within 1.5 x those of the lowest swept rate ``base``:
    past the knee the queue adds its wait to both. (Comparing a window's
    thirds reads the shuffle of a few long prompts at low rates.)"""
    return (row["failed"] == 0
            and row["ttft_p50_ms"] <= 1.5 * base["ttft_p50_ms"]
            and row["tpot_p50_ms"] <= 1.5 * base["tpot_p50_ms"])


def knee_of(rows: list) -> float | None:
    """The highest swept rate up to which every swept rate is
    sustained."""
    rows = sorted(rows, key=lambda r: r["rate"])
    knee = None
    for row in rows:
        if row["ttft_p50_ms"] is None or not sustained(row, rows[0]):
            break
        knee = row["rate"]
    return knee


def summary(rate, win, spec, shape, chips, seconds) -> dict:
    """One swept rate's window: requests offered, finished inside it,
    still unfinished at its end, failed; output tokens/s; TTFT and TPOT
    percentiles; compiles and preemptions in it."""
    from bench import measure
    from bench.measure import percentile
    res = win.result
    run = measure.Run(records=res["requests"], t0=res["t0"], t1=res["t1"],
                      mode="open", setup_s=0.0, shape=shape, chips=chips,
                      peak_flops=float("nan"),
                      prompts=[len(r["prompt"]) for r in spec["requests"]])
    finished = sum(1 for r in run.records if r["finish"] is not None
                   and r["events"] and r["events"][-1][0] <= run.t1)
    ttft, tpot = run.ttft_ms(), run.tpot_ms()
    third = [[t for t, r in zip(ttft, run.records)
              if k * seconds / 3 <= r["due"] < (k + 1) * seconds / 3]
             for k in (0, 2)]
    return {
        "rate": rate, "offered": len(run.records),
        "finished_in_window": finished,
        "unfinished_at_window_end": len(run.records) - finished,
        "failed": sum(map(measure.failed, run.records)),
        "output_tokens_per_s": run.output_tokens_in_window() / seconds,
        "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
        "ttft_p90_ms": percentile(ttft, 90) if ttft else None,
        "tpot_p50_ms": percentile(tpot, 50) if tpot else None,
        "tpot_p90_ms": percentile(tpot, 90) if tpot else None,
        "ttft_p50_first_third_ms": (percentile(third[0], 50)
                                    if third[0] else None),
        "ttft_p50_last_third_ms": (percentile(third[1], 50)
                                   if third[1] else None),
        "compiles_in_window": win.compiles_in_window,
        "preemptions": (win.counters1.get("preemptions", 0)
                        - win.counters0.get("preemptions", 0))}


if __name__ == "__main__":
    sys.exit(main())
