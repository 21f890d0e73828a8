"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Sets up the served path of the cell's configuration (weights from the
seed, the engine, the server on an ephemeral port), warms every program
the cell's traffic can reach, measures ``--seconds`` of that traffic
from a load generator in a child process, checks what was served
against the plain float32 reference, and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each number compared beside its limit, also printed as
the last lines of standard error.

Exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for. JAX's persistent compilation cache is
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import harness
    t_start = harness.process_start()
    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: cannot import the program: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=t_start)
    except SystemExit as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
