"""Readings that set a cell's limit on ``gap_max``, on the chip, in one
process.

    python3 bench/control.py --workload phi3-mini-3.8b.chat \
        --seeds 11,12,13 --seconds 30

For each seed: a run of the cell at its own size and load over a short
window (with no warm-up: the readings time nothing), then the reference over the served sample, as ``bench/run.py``
does, and the control: the same forward with every projection in
float8, put in the program's place, judged by the same checks (the gap
of the token it puts first at each position of the same sequences).
Prints one JSON line per seed with both sides' gap statistics. The
lower reading is the largest ``gap_max`` of the program over the seeds,
the upper the smallest ``gap_max`` of the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            line = harness.run_cell(args.workload, seed, args.seconds, False,
                                    control=True, warm=False)
        except SystemExit as e:
            print(f"control: {e}", file=sys.stderr)
            return 1
        checks = line["checks"]
        print(json.dumps({
            "seed": seed, "correct": line["correct"],
            "attempted": line["attempted"], "failed": line["failed"],
            "gap_max": checks.get("gap_max", {}).get("value"),
            "control_correct": line["control"]["correct"],
            "readings": line["control"]["program"],
            "wrong_requests": checks["wrong_requests"]["value"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
