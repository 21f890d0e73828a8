"""The paper's §4.1 patterns, runnable on 8 simulated devices:
BSP baseline vs Pull/Push-style ring collective matmul vs the fused
in-kernel-DMA Pallas kernel — all checked against each other.

    PYTHONPATH=src python examples/ag_gemm_patterns.py
(This example sets the fake-device flag itself; run it standalone.)
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collective_matmul as cm
from repro.core import taxes
from repro.kernels import ops
from repro.launch.mesh import make_mesh


def main():
    W = 8
    mesh = make_mesh((W,), ("model",))
    M, K, N = 128, 1024, 512
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    a_sh = jax.device_put(a, NamedSharding(mesh, P(None, "model")))
    want = np.asarray(a @ b)

    print(f"AG+GEMM  A({M},{K}) K-sharded over {W} devices, B({K},{N})")
    for mode in ("bsp", "ring", "ring_bidir"):
        got = jax.jit(lambda a, b, m=mode: cm.ag_gemm_k_sharded_sm(
            a, b, mesh, mode=m))(a_sh, b)
        err = float(np.max(np.abs(np.asarray(got) - want)))
        print(f"  {mode:11s} max_err={err:.2e}  OK")

    got = jax.jit(lambda a, b: ops.ag_gemm(a, b, mesh, bn=128))(a_sh, b)
    err = float(np.max(np.abs(np.asarray(got) - want)))
    print(f"  {'pallas-fused':11s} max_err={err:.2e}  OK "
          f"(single kernel, in-VMEM handoff, remote DMA ring)")

    print("\nThree-Taxes model (TPU v5e projection, paper's shapes):")
    for M_p in (16, 128, 1024):
        op = taxes.ag_gemm_op_shape(M_p, 8192, 28672, 8)
        t_bsp = taxes.bsp_schedule(op).total_s * 1e6
        t_ring = taxes.ring_schedule(op, bidir=True).total_s * 1e6
        print(f"  M={M_p:5d}: BSP {t_bsp:8.1f}us  fused {t_ring:8.1f}us  "
              f"speedup {t_bsp / t_ring:.2f}x")


if __name__ == "__main__":
    main()
