"""Time single-device paged decode attention on both sides of
``flash_decode.paged_view``: every slot reading the whole pool in place,
against each slot's table blocks gathered (the float32 oracle), per
layer, at one model's attention shape.

    PYTHONPATH=src python -m benchmarks.paged_view_micro \\
        [--config phi3-mini-3.8b] [--batch 16] [--n-blocks 256] \\
        [--block 16] [--layers 32] [--widths 1,2,4,8,16,32,64,100] \\
        [--out FILE]

Prints one JSON line per table width: milliseconds per layer for each
view (median of ``--reps`` timed calls of a ``--layers`` scan), and the
view the rule picks there. Each slot's table holds ``width`` distinct
blocks and its length fills them. Only a run on the accelerator says
where the views cross.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def time_views(cfg, batch, n_blocks, bs, layers, widths, reps):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core import flash_decode as fd

    H, KVH, D = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    shape = (layers, n_blocks, bs, KVH, D)
    k_pools = jax.random.normal(kk, shape, jnp.bfloat16)
    v_pools = jax.random.normal(kv, shape, jnp.bfloat16)
    q = jax.random.normal(kq, (batch, H, D), jnp.bfloat16)
    scale = 1.0 / D ** 0.5
    for width in widths:
        tables = ((jnp.arange(batch)[:, None] * width
                   + jnp.arange(width)[None, :]) % n_blocks).astype(jnp.int32)
        cur = jnp.full((batch,), width * bs, jnp.int32)
        row = {"width": width, "batch": batch, "n_blocks": n_blocks,
               "rule": fd.paged_view(1, batch, width, n_blocks)}
        for view, whole in (("pool", True), ("gather", False)):
            @jax.jit
            def run(q, k_pools, v_pools, tables, cur, whole=whole):
                def layer(acc, kv):
                    o = fd.paged_decode_attention(
                        q, kv[0], kv[1], cur, tables, scale,
                        whole_pool=whole)
                    return acc + o.astype(jnp.float32), None
                acc, _ = lax.scan(layer, jnp.zeros(q.shape, jnp.float32),
                                  (k_pools, v_pools))
                return acc

            run(q, k_pools, v_pools, tables, cur).block_until_ready()
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                run(q, k_pools, v_pools, tables, cur).block_until_ready()
                times.append(time.perf_counter() - t0)
            row[f"{view}_ms_per_layer"] = (
                1e3 * statistics.median(times) / layers)
        yield row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="phi3-mini-3.8b")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--n-blocks", type=int, default=256)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--widths", default="1,2,4,8,16,32,64,100")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default=None,
                    help="also append the JSON lines to this file")
    a = ap.parse_args(argv)
    from repro.configs import get_config

    cfg = get_config(a.config)
    widths = [int(w) for w in a.widths.split(",")]
    for row in time_views(cfg, a.batch, a.n_blocks, a.block, a.layers,
                          widths, a.reps):
        line = json.dumps(row)
        print(line, flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(line + "\n")


if __name__ == "__main__":
    main()
