"""Benchmark harness — one table per paper figure.

Prints ``name,us_per_call,derived`` CSV.

  Table 1 (paper Fig. 9):  AG+GEMM M-sweep, BSP vs ring vs bidir ring
  Table 2 (paper Fig. 10): Flash Decode KV-length sweep, evolution ladder
  Table 3 (paper Fig. 11): Flash Decode device-count scaling
  Table 4 (paper Fig. 2):  Three-Taxes analytical decomposition
  Table 5:                 local Pallas matmul kernel vs XLA dot

Multi-device tables run in a subprocess with 8 fake host devices (this
process keeps 1 device per the dry-run hygiene rule). Wall-clock on fake
CPU devices measures structure, not ICI; the ``derived`` column carries
the TPU-projected model numbers used in EXPERIMENTS.md.
"""
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _table(title):
    print(f"# --- {title} ---", flush=True)


def _sub(which, devices=8):
    """Run one distributed_bench table on fake CPU devices; returns
    whether the child succeeded."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.setdefault("PYTHONPATH", "src")
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "distributed_bench.py"), which],
        env=env, capture_output=True, text=True, timeout=1800,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if out.returncode:
        print(f"subprocess_error_{which},0,{out.stderr[-300:]!r}")
    for line in out.stdout.splitlines():
        if "," in line and not line.startswith("#"):
            print(line, flush=True)
    return out.returncode == 0


def table_taxes():
    from repro.core import taxes
    _table("table4: Three-Taxes decomposition (TPU v5e model, W=8)")
    for M in (16, 64, 256, 1024):
        op = taxes.ag_gemm_op_shape(M, 8192, 28672, 8)
        for sched, rep in (("bsp", taxes.bsp_schedule(op)),
                           ("ring", taxes.ring_schedule(op)),
                           ("bidir", taxes.ring_schedule(op, bidir=True))):
            print(f"taxes_aggemm_M{M}_{sched},{rep.total_s*1e6:.2f},"
                  f"launch={rep.launch_tax_s*1e6:.2f}us;"
                  f"bulk={rep.bulk_sync_tax_s*1e6:.2f}us;"
                  f"locality={rep.locality_tax_s*1e6:.2f}us")


def table_local_matmul():
    import jax
    import jax.numpy as jnp
    from repro.kernels.matmul import matmul
    _table("table5: local Pallas matmul (interpret) vs XLA dot")
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 512), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    f_ker = jax.jit(lambda a, b: matmul(a, b, bm=128, bk=128, bn=128))
    f_xla = jax.jit(lambda a, b: a @ b)
    for name, fn in (("pallas_matmul_interp", f_ker), ("xla_dot", f_xla)):
        jax.block_until_ready(fn(a, b))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn(a, b)
        jax.block_until_ready(out)
        us = (time.perf_counter() - t0) / 5 * 1e6
        print(f"{name},{us:.1f},shape=256x512x256")


def main() -> None:
    ok = []
    _table("table1: AG+GEMM M-sweep (paper Fig. 9)")
    ok.append(_sub("ag_gemm"))
    _table("table2: Flash Decode KV sweep (paper Fig. 10)")
    ok.append(_sub("flash_decode"))
    _table("table3: Flash Decode scaling (paper Fig. 11)")
    ok.append(_sub("scaling"))
    table_taxes()
    table_local_matmul()
    _table("pallas fused AG+GEMM (structural, interpret mode)")
    ok.append(_sub("pallas", devices=4))
    if not all(ok):
        sys.exit(1)


if __name__ == "__main__":
    main()
