"""End-to-end training driver with fault tolerance.

Features (see DESIGN.md §5): FSDP/TP/DP sharded train step, async atomic
checkpointing + resume (including onto a different mesh — elastic),
SIGTERM preemption handling, straggler watchdog, heartbeats, optional
gradient compression across the pod axis, deterministic seekable data.

Example (CPU, tiny model):
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
      --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt --ckpt-every 20
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config, smoke_config
from repro.data.pipeline import SyntheticLM
from repro.distributed import context as dctx
from repro.distributed.fault_tolerance import (Heartbeat, PreemptionGuard,
                                               StragglerWatchdog)
from repro.distributed.sharding_rules import rules_for
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import lm
from repro.optim import adamw, schedule


def build_mesh(args):
    if args.mesh == "production":
        return make_production_mesh(multi_pod=args.multi_pod)
    n = len(jax.devices())
    model = min(args.tp, n)
    return make_host_mesh(data=n // model, model=model)


def main(argv=None):
    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="use the reduced config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=20)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mesh", default="host", choices=("host", "production"))
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--fusion-mode", default="auto",
                   choices=("auto", "bsp", "ring"))
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=100)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--grad-compress", default="none",
                   choices=("none", "bf16", "int8"))
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--heartbeat-file", default=None)
    p.add_argument("--metrics-file", default=None)
    args = p.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = build_mesh(args)
    rules = rules_for(cfg, mesh)
    ctx = dctx.make_context(mesh, fusion_mode=args.fusion_mode, rules=rules)

    opt_cfg = adamw.AdamWConfig(
        lr=schedule.warmup_cosine(args.lr, args.warmup, args.steps))
    guard = PreemptionGuard().install()
    watchdog = StragglerWatchdog()
    hb = Heartbeat(args.heartbeat_file) if args.heartbeat_file else None

    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed)

    with dctx.use(ctx), mesh:
        psh = steps_lib.param_shardings(cfg, rules)
        params = lm.init_params_sharded(jax.random.PRNGKey(args.seed), cfg,
                                        rules)
        osh = steps_lib.opt_state_shardings(cfg, rules, psh)
        opt_state = jax.jit(adamw.init_state, out_shardings=osh)(params)

        start_step = 0
        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        if ckpt and args.resume and ckpt.latest_step() is not None:
            # elastic restore: works for ANY current mesh
            state_t = {"params": params, "opt": opt_state}
            restored, manifest = ckpt.restore(
                None, state_t, shardings={"params": psh, "opt": osh})
            params, opt_state = restored["params"], restored["opt"]
            start_step = manifest["extra"].get("next_step", 0)
            print(f"[train] resumed at step {start_step} "
                  f"on mesh {dict(mesh.shape)}")

        step_fn = steps_lib.make_train_step(cfg, opt_cfg)
        jitted = jax.jit(step_fn, in_shardings=(psh, osh, None),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1))

        metrics_log = []
        t_last = time.time()
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     data.batch_at(step).items()}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                dt = time.time() - t_last
                t_last = time.time()
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({dt:.2f}s)")
                metrics_log.append({"step": step, "loss": loss})
                if hb:
                    hb.beat(step, loss=loss)
            watchdog.record(step, time.time() - t_last)

            if ckpt and ((step + 1) % args.ckpt_every == 0
                         or guard.preempted):
                ckpt.save(step + 1, {"params": params, "opt": opt_state},
                          extra={"next_step": step + 1,
                                 "mesh": dict(mesh.shape)},
                          block=guard.preempted)
            if guard.preempted:
                print(f"[train] preempted at step {step}; "
                      f"checkpoint saved, exiting cleanly")
                break

        if ckpt:
            ckpt.save(args.steps, {"params": params, "opt": opt_state},
                      extra={"next_step": args.steps}, block=True)
            ckpt.wait()
        if watchdog.slow_steps:
            print(f"[train] straggler summary: {watchdog.summary()}")
        if args.metrics_file:
            with open(args.metrics_file, "w") as f:
                json.dump(metrics_log, f)
        return metrics_log


if __name__ == "__main__":
    main()
