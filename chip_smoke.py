"""Serve phi3-mini-3.8b at its published widths and full depth on a TPU,
end to end, and check what comes back.

    python chip_smoke.py             # one chip: client -> server -> engine
    python chip_smoke.py --chips 4   # four chips: TP=4 decode vs TP=1

One chip: an engine built by ``launch.server.build_engine`` (bf16
weights from ``--seed``) runs behind ``launch.server.Server`` on an
ephemeral port. Eight requests go over the wire through
``serving.client``: half together, half staggered, so that both the
mixed and the pure megatick programs run. Every request must finish
``length`` with ``max_new`` ids in ``[0, vocab)``, and one prompt served
alone on a fresh engine must give the same greedy ids as in the batch.

Four chips: phi3-mini on a (data 1, model 4) mesh is served under the
``auto`` and ``ring`` fusion modes, its params must be spread over the
four devices, and the logits of ``lm.decode_step`` over the paged
state for one prompt, in each mode, must agree with TP=1 on one of the
four devices.

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``. Without a TPU the script exits non-zero and prints
no such line. It runs in one process: a chip belongs to one process.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "phi3-mini-3.8b"
# batch 8, max_len 512, block 16, K=4: the default pool is 4,096 tokens
# x 393,216 B = 1.6 GB, and a dispatch holds two pools (nothing is
# donated), beside 7.6 GB of bf16 weights on a 16 GB chip.
SERVER_ARGV = ("--arch", ARCH, "--batch", "8", "--max-len", "512",
               "--block-size", "16", "--decode-steps", "4")
N_REQUESTS = 8
PROMPT_LENS = (64, 192)
MAX_NEW = 32
# TP=4 against TP=1, both over the same bf16 weights with float32
# activations at the highest matmul precision: sharding changes only the
# order of float32 sums, which 32 random layers amplify and the bf16 cast
# of the logits (``lm.logits_fn``) quantizes. On a v5e 2x2 host this gave
# 7.1e-4 relative in both modes; on CPU devices, 2e-6. In bf16
# activations the same comparison differs by about 1e-1 from rounding
# alone, which would hide a fault. A wrong shard or a missing combine
# gives differences of order 1.
TP_LOGITS_RTOL = 1e-3


class CompileLog:
    """Counts backend compiles (persistent-cache loads included) and
    the seconds they took, from JAX's own monitoring events."""

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

    def line(self) -> str:
        return (f"compiles {self.n} ({self.cache_hits} from the persistent "
                f"cache) took {self.seconds:.3f} s")


def device_param_bytes(params, device) -> int:
    """Bytes of the param shards that live on ``device``."""
    import jax
    return sum(s.data.nbytes for leaf in jax.tree.leaves(params)
               for s in leaf.addressable_shards if s.device == device)


def make_prompts(vocab: int, seed: int, n: int = N_REQUESTS):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=n)
    return [[int(t) for t in rng.integers(1, vocab, size=int(k))]
            for k in lens]


async def _serve(engine, prompts, max_new):
    """Run a Server over ``engine`` and send ``prompts``: the first half
    together, then each of the rest once an earlier request has streamed
    its first tokens. Returns the client Completions in prompt order."""
    from repro.launch.server import Server
    from repro.serving import client

    server = Server(engine, port=0)
    await server.start()
    try:
        half = max(1, len(prompts) // 2)
        started = [asyncio.Event() for _ in prompts]

        def on_event(i):
            return lambda ev: started[i].set()

        async def one(i):
            return await client.complete(
                server.host, server.port, prompts[i],
                max_new_tokens=max_new, on_event=on_event(i))

        tasks = [asyncio.create_task(one(i)) for i in range(half)]
        for i in range(half, len(prompts)):
            await started[i - half].wait()
            tasks.append(asyncio.create_task(one(i)))
        return await asyncio.gather(*tasks)
    finally:
        await server.stop()


def check_completions(outs, max_new: int, vocab: int):
    for i, c in enumerate(outs):
        if c.status != 200 or c.error is not None \
                or c.finish_reason != "length":
            raise AssertionError(
                f"request {i}: status {c.status} finish "
                f"{c.finish_reason!r} error {c.error!r}")
        if len(c.token_ids) != max_new \
                or not all(0 <= t < vocab for t in c.token_ids):
            raise AssertionError(
                f"request {i}: {len(c.token_ids)} ids, want {max_new} "
                f"in [0, {vocab}): {c.token_ids}")


def serve_phase(server_argv, devices, seed: int = 0):
    """The one-chip path: batch of requests over the wire, then the
    first prompt alone on a fresh engine over the same weights."""
    from repro.launch.server import build_engine, make_parser

    args = make_parser().parse_args(list(server_argv)
                                    + ["--seed", str(seed)])
    engine = build_engine(args, devices=devices)
    cfg, params = engine.cfg, engine.params
    print(f"[smoke] {cfg.name}: layers {cfg.n_layers}, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads} (kv {cfg.n_kv_heads}), "
          f"head_dim {cfg.hd}, vocab {cfg.vocab_size}, params "
          f"{params['embed']['table'].dtype}", flush=True)
    print(f"[smoke] parameter bytes on {devices[0]}: "
          f"{device_param_bytes(params, devices[0])}", flush=True)
    prompts = make_prompts(cfg.vocab_size, seed)
    t0 = time.perf_counter()
    outs = asyncio.run(_serve(engine, prompts, MAX_NEW))
    wall = time.perf_counter() - t0
    check_completions(outs, MAX_NEW, cfg.vocab_size)
    counts = {"mixed": engine.mixed_dispatch_count,
              "pure": engine.decode_dispatch_count}
    if not (counts["mixed"] and counts["pure"]):
        raise AssertionError(f"both megatick programs must run: {counts}")
    print(f"[smoke] batch: {len(outs)} requests, prompt tokens "
          f"{sum(map(len, prompts))}, new tokens "
          f"{sum(len(c.token_ids) for c in outs)}, wall {wall:.3f} s "
          f"(compiles included), dispatches mixed {counts['mixed']} "
          f"pure {counts['pure']}", flush=True)
    del engine
    gc.collect()

    solo_engine = build_engine(args, devices=devices, params=params)
    solo = asyncio.run(_serve(solo_engine, prompts[:1], MAX_NEW))
    check_completions(solo, MAX_NEW, cfg.vocab_size)
    if solo[0].token_ids != outs[0].token_ids:
        raise AssertionError(f"solo ids {solo[0].token_ids} != batched "
                             f"{outs[0].token_ids}")
    print("[smoke] every request finished 'length'; solo and batched "
          "ids identical", flush=True)
    return outs


def prompt_logits(engine, prompt, dtype=None):
    """Logits after ``prompt`` from ``lm.decode_step`` (scanned by
    ``lm.decode_chunk``) over a fresh paged state laid out by the
    engine's mesh rules, as float32 numpy. ``dtype`` replaces the
    activation dtype; float32 also runs at the highest matmul
    precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed import context as dctx
    from repro.models import lm
    from repro.serving.kv_cache import blocks_for

    cfg, bs, ctx = engine.cfg, engine.pool.block_size, engine.ctx
    if dtype is not None:
        cfg = cfg.replace(dtype=dtype)
    precision = "highest" if cfg.dtype == jnp.float32 else None
    n_blocks = blocks_for(len(prompt), bs)
    n_blocks += (-n_blocks) % ctx.model_axis_size
    state = lm.init_paged_decode_state_sharded(cfg, 1, n_blocks, bs,
                                               n_blocks, ctx.rules)
    state["block_tables"] = jnp.arange(n_blocks, dtype=jnp.int32)[None]
    tokens = jnp.asarray([prompt], jnp.int32)
    counts = jnp.asarray([len(prompt)], jnp.int32)

    def logits(p, t, c, s):
        with dctx.use(ctx), jax.default_matmul_precision(precision):
            return lm.decode_chunk(p, t, c, s, cfg)[0]

    return np.asarray(jax.jit(logits)(engine.params, tokens, counts, state),
                      np.float32)[0, 0]


def tp_phase(server_argv, devices, seed: int = 0):
    """The four-chip path: TP=1 logits on ``devices[0]``, then for each
    fusion mode a TP=4 engine that serves requests over the wire and
    whose logits for the same prompt must agree with TP=1."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.server import build_engine, make_parser

    def engine_for(extra, devs):
        args = make_parser().parse_args(
            list(server_argv) + ["--seed", str(seed)] + list(extra))
        return build_engine(args, devices=devs)

    ref_engine = engine_for(["--tp", "1"], devices[:1])
    cfg = ref_engine.cfg
    prompts = make_prompts(cfg.vocab_size, seed, n=4)
    ref = prompt_logits(ref_engine, prompts[0], jnp.float32)
    ref_bf16 = prompt_logits(ref_engine, prompts[0])
    print(f"[smoke] TP=1 on {devices[0]}: logits |max| "
          f"{float(np.abs(ref).max()):.4f}", flush=True)
    del ref_engine
    gc.collect()

    for mode in ("auto", "ring"):
        engine = engine_for(["--tp", "4", "--fusion-mode", mode],
                            devices[:4])
        total = sum(x.nbytes for x in jax.tree.leaves(engine.params))
        per_dev = [device_param_bytes(engine.params, d)
                   for d in devices[:4]]
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices[:4]]
        print(f"[smoke] TP=4 {mode}: param bytes {total}, per device "
              f"{per_dev}, bytes in use per device {in_use}", flush=True)
        if not all(b > 0 for b in per_dev) or max(per_dev) > total / 2:
            raise AssertionError(f"params not spread over four devices: "
                                 f"{per_dev} of {total}")
        outs = asyncio.run(_serve(engine, prompts, MAX_NEW))
        check_completions(outs, MAX_NEW, cfg.vocab_size)
        got = prompt_logits(engine, prompts[0], jnp.float32)
        rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
        got_bf16 = prompt_logits(engine, prompts[0])
        rel_bf16 = float(np.linalg.norm(got_bf16 - ref_bf16)
                         / np.linalg.norm(ref_bf16))
        print(f"[smoke] TP=4 {mode}: {len(outs)} requests finished "
              f"'length'; float32 logits relative L2 error vs TP=1 "
              f"{rel:.3e} (limit {TP_LOGITS_RTOL}), argmax "
              f"{int(got.argmax())} vs {int(ref.argmax())}; bf16 "
              f"logits {rel_bf16:.3e} (not gated)", flush=True)
        if not rel <= TP_LOGITS_RTOL:
            raise AssertionError(f"TP=4 {mode} logits differ from TP=1: "
                                 f"relative error {rel}")
        del engine
        gc.collect()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="1: the served path on one chip; 4: TP=4 "
                        "decode (auto, ring) against TP=1, and nothing "
                        "else")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    compiles = CompileLog()
    print(f"[smoke] {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        tp_phase(SERVER_ARGV, devices[:4], seed=args.seed)
    else:
        serve_phase(SERVER_ARGV, devices[:1], seed=args.seed)
    print(f"[smoke] {compiles.line()}; total wall "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        print(f"[smoke] {d}: peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use', 'not reported')}",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
