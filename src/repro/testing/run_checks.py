"""Entry point for the multi-device check battery (see distributed_checks).

Must be launched with XLA_FLAGS=--xla_force_host_platform_device_count=N.
Each check runs in its OWN subprocess: jit-cache state shared across
differing meshes in one process can trip an XLA CHECK crash
("Invalid binary instruction opcode copy"), and process isolation also
means one crash can't take down the whole battery.

Prints one JSON object mapping check name -> {ok, error}.
"""
import concurrent.futures
import json
import os
import subprocess
import sys
import traceback


def run_one(name: str) -> dict:
    code = (f"import sys; sys.path.insert(0, {os.getcwd()!r} + '/src'); "
            f"from repro.testing import distributed_checks as dc; "
            f"dc.{name}()")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("XLA_FLAGS",
                   "--xla_force_host_platform_device_count=8")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode == 0:
        return {"ok": True}
    return {"ok": False,
            "error": (proc.stderr[-3000:] or
                      f"exit code {proc.returncode}")}


def _run_contained(name: str) -> dict:
    try:
        return run_one(name)
    except Exception:
        return {"ok": False, "error": traceback.format_exc()[-3000:]}


def main():
    from repro.testing import distributed_checks as dc
    names = [fn.__name__ for fn in dc.ALL_CHECKS]
    # a few check processes at a time: one after another, the battery
    # alone would take most of the test suite's time limit
    workers = max(1, min(4, (os.cpu_count() or 2) // 2))
    results = {}
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        for name, res in zip(names, pool.map(_run_contained, names)):
            results[name] = res
            print(f"# {name}: {'OK' if res['ok'] else 'FAIL'}", flush=True)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
