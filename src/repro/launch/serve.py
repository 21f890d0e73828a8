"""Serving driver: load (or init) a model and serve batched requests
through the continuous-batching engine over the flash-decode path.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --requests 16 --batch 4 --max-new 8
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config, smoke_config
from repro.distributed import context as dctx
from repro.distributed.sharding_rules import rules_for
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.serving.engine import Engine, Request
from repro.serving.metrics import percentile


def main(argv=None):
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--ckpt-dir", default=None,
                   help="restore params from a training checkpoint")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--prefill-chunk", type=int, default=8,
                   help="prompt tokens consumed per slot per tick")
    p.add_argument("--decode-steps", type=int, default=1,
                   help="decode megatick length K: one jitted dispatch "
                        "runs K decode steps with sampling "
                        "device-resident, returning token ids instead "
                        "of K logit tensors; batches with prefill in "
                        "flight take the fused mixed program "
                        "(1 = the byte-identical single-step path)")
    p.add_argument("--megatick-token-budget", type=int, default=None,
                   help="per-slot token quota of a MIXED megatick "
                        "(prompt tokens + piggybacked decode steps per "
                        "slot per dispatch); default "
                        "max(decode-steps, prefill-chunk), must be >= "
                        "decode-steps")
    p.add_argument("--stagger", type=int, default=0,
                   help="admit request i no earlier than tick i*STAGGER "
                        "(0 = all at once)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--fusion-mode", default="auto",
                   choices=("auto", "bsp", "ring"))
    p.add_argument("--sampler", default="greedy",
                   choices=("greedy", "temperature"))
    p.add_argument("--scheduler", default="fcfs",
                   choices=("fcfs", "priority", "slo"),
                   help="admission/preemption policy: fcfs (submission "
                        "order), priority (Request.priority with aging), "
                        "slo (earliest-deadline-first on --deadline-ms)")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="per-request TTFT target tagged onto every "
                        "request (the slo scheduler runs tagged requests "
                        "earliest-deadline-first)")
    p.add_argument("--temp", type=float, default=1.0,
                   help="sampling temperature (temperature sampler)")
    p.add_argument("--top-k", type=int, default=0,
                   help="top-k truncation, 0 = full vocab")
    p.add_argument("--block-size", type=int, default=16,
                   help="paged-KV block granularity (tokens)")
    p.add_argument("--kv-blocks", type=int, default=None,
                   help="KV pool size in blocks (default: contiguous "
                        "parity, batch*max_len worth)")
    p.add_argument("--paged-gather", default="bounded",
                   choices=("bounded", "masked"),
                   help="distributed paged attention work model: gather "
                        "each slot's blocks through its table (per-slot "
                        "work bounded at gather_width*block_size) or "
                        "score the whole masked pool shard (the "
                        "token-identity oracle)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--metrics-file", default=None)
    args = p.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if not cfg.has_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    # a server keeps no master weights: params live in the compute dtype
    cfg = cfg.replace(param_dtype=cfg.dtype)

    n = len(jax.devices())
    model = min(args.tp, n)
    mesh = make_host_mesh(data=n // model, model=model)
    ctx = dctx.make_context(mesh, fusion_mode=args.fusion_mode,
                            rules=rules_for(cfg, mesh))

    with dctx.use(ctx), mesh:
        params = lm.init_params_sharded(jax.random.PRNGKey(args.seed), cfg,
                                        ctx.rules)
        if args.ckpt_dir:
            ck = Checkpointer(args.ckpt_dir)
            tree, manifest = ck.restore(
                None, {"params": params},
                shardings={"params": jax.tree.map(lambda x: x.sharding,
                                                  params)})
            # training checkpoints hold float32 masters
            params = jax.tree.map(lambda t, x: x.astype(t.dtype), params,
                                  tree["params"])
            print(f"[serve] restored step {manifest['step']}")

        eng = Engine(params, cfg, batch=args.batch, max_len=args.max_len,
                     prefill_chunk=args.prefill_chunk,
                     sampler=args.sampler, seed=args.seed,
                     block_size=args.block_size, n_blocks=args.kv_blocks,
                     scheduler=args.scheduler,
                     decode_steps=args.decode_steps,
                     megatick_token_budget=args.megatick_token_budget,
                     bounded_gather=args.paged_gather == "bounded")
        rng = jax.random.PRNGKey(args.seed + 1)
        for i in range(args.requests):
            rng, k = jax.random.split(rng)
            plen = 2 + int(jax.random.randint(k, (), 0, 6))
            plen = min(plen, max(1, args.max_len - 2))
            prompt = [int(t) for t in
                      jax.random.randint(k, (plen,), 1, cfg.vocab_size)]
            eng.submit(Request(rid=i, prompt=prompt,
                               max_new_tokens=args.max_new,
                               temp=args.temp, top_k=args.top_k,
                               deadline_ms=args.deadline_ms),
                       at_tick=i * args.stagger)
        t0 = time.time()
        done = eng.run()
        dt = time.time() - t0
        toks = sum(len(r.out_tokens) for r in done)
        lat = [r.finished_t - r.submitted_t for r in done]
        stats = {"requests": len(done), "new_tokens": toks,
                 "wall_s": round(dt, 3),
                 "tok_per_s": round(toks / dt, 2),
                 "p50_latency_s": round(percentile(lat, 50), 3),
                 "p99_latency_s": round(percentile(lat, 99), 3),
                 **eng.metrics(done)}
        print(f"[serve] {stats}")
        if args.metrics_file:
            with open(args.metrics_file, "w") as f:
                json.dump(stats, f)
        return stats


if __name__ == "__main__":
    main()
