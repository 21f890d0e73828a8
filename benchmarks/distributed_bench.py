"""Multi-device benchmark bodies (run in a subprocess with fake devices).

Emits CSV lines: name,us_per_call,derived
Wall-clock on fake CPU devices measures *structure* (kernel counts,
serialization), not ICI overlap — the roofline/tax model supplies the
TPU-projected numbers next to each measurement.
"""
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, "src")

from repro.core import collective_matmul as cm          # noqa: E402
from repro.core import flash_decode as fd               # noqa: E402
from repro.core import taxes                            # noqa: E402
from repro.kernels import ops                           # noqa: E402
from repro.launch.mesh import make_mesh                 # noqa: E402
from repro.serving.kv_cache import pow2_bucket          # noqa: E402


def timeit(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6     # us


def bench_ag_gemm(W=8):
    """Paper Figure 9: AG+GEMM speedup vs M (K=8192 N=28672 scaled down
    16x for CPU: K=512, N=1792)."""
    mesh = make_mesh((W,), ("model",))
    K, N = 512, 1792
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    for M in (16, 64, 256, 1024):
        a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
        a_sh = jax.device_put(a, NamedSharding(mesh, P(None, "model")))
        fns = {
            "bsp": jax.jit(lambda a, b: cm.ag_gemm_k_sharded_sm(
                a, b, mesh, mode="bsp")),
            "ring": jax.jit(lambda a, b: cm.ag_gemm_k_sharded_sm(
                a, b, mesh, mode="ring")),
            "ring_bidir": jax.jit(lambda a, b: cm.ag_gemm_k_sharded_sm(
                a, b, mesh, mode="ring_bidir")),
        }
        # modeled TPU latency ratio from the taxes framework
        op = taxes.ag_gemm_op_shape(M, 8192, 28672, W)
        model_speedup = (taxes.bsp_schedule(op).total_s
                         / taxes.ring_schedule(op, bidir=True).total_s)
        for mode, fn in fns.items():
            us = timeit(fn, a_sh, b)
            print(f"ag_gemm_M{M}_{mode},{us:.1f},"
                  f"modeled_tpu_speedup_vs_bsp={model_speedup:.3f}")


def bench_flash_decode(W=8):
    """Paper Figure 10: Flash Decode vs global KV length (evolution)."""
    mesh = make_mesh((W,), ("model",))
    B, H, KVH, D = 1, 96, 8, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, D), jnp.float32)
    for S in (4096, 16384, 65536):
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, D),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KVH, D),
                              jnp.bfloat16)
        sh = NamedSharding(mesh, P(None, "model", None, None))
        k_sh, v_sh = jax.device_put(k, sh), jax.device_put(v, sh)
        cur = jnp.int32(S - 3)
        op = taxes.flash_decode_op_shape(B, H, D, S, KVH, W)
        model_speedup = (taxes.bsp_schedule(op).total_s
                         / taxes.ring_schedule(op).total_s)
        for mode in ("bsp", "ring", "rs_ag"):
            fn = jax.jit(lambda q, k, v, c, m=mode: fd.decode_attention_sm(
                q, k, v, c, mesh, scale=0.125, mode=m))
            us = timeit(fn, q, k_sh, v_sh, cur, iters=10)
            print(f"flash_decode_S{S}_{mode},{us:.1f},"
                  f"modeled_tpu_speedup_vs_bsp={model_speedup:.3f}")


def bench_scaling():
    """Paper Figure 11: Flash Decode scaling with device count."""
    B, H, KVH, D, S = 1, 96, 8, 64, 32768
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, D), jnp.float32)
    n = len(jax.devices())
    for W in (1, 2, 4, 8):
        if W > n:
            continue
        mesh = make_mesh((W,), ("model",))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, D),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KVH, D),
                              jnp.bfloat16)
        sh = NamedSharding(mesh, P(None, "model", None, None))
        k_sh, v_sh = jax.device_put(k, sh), jax.device_put(v, sh)
        fn = jax.jit(lambda q, k, v, c: fd.decode_attention_sm(
            q, k, v, c, mesh, scale=0.125, mode="ring"))
        us = timeit(fn, q, k_sh, v_sh, jnp.int32(S - 1), iters=10)
        op = taxes.flash_decode_op_shape(B, H, D, S, KVH, W)
        t_tpu = taxes.ring_schedule(op).total_s
        print(f"flash_decode_scaling_W{W},{us:.1f},"
              f"modeled_tpu_total_us={t_tpu * 1e6:.2f}")


def bench_serving_engine():
    """Continuous-batching engine under staggered traffic: lockstep
    token-at-a-time prefill (chunk=1) vs chunked batched prefill.
    Derived columns: jitted dispatches to drain the same workload (idle
    ticks excluded) — the quantity chunked prefill cuts — and the paged
    pool's block-occupancy high-water mark, the quantity that bounds
    how much HBM the workload actually pinned."""
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=2)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size, rng.integers(4, 24)))
               for _ in range(12)]
    for chunk in (1, 8):
        eng = Engine(params, cfg, batch=4, max_len=128,
                     prefill_chunk=chunk)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=[int(t) for t in p],
                               max_new_tokens=8), at_tick=i)
        t0 = time.perf_counter()
        done = eng.run()
        dt = (time.perf_counter() - t0) * 1e6
        m = eng.metrics(done)
        print(f"serve_staggered_chunk{chunk},{dt:.1f},"
              f"dispatches={m['dispatches']};p50_ttft_s={m['p50_ttft_s']};"
              f"kv_blocks_hwm={m['kv_blocks_hwm']}/{m['kv_blocks']};"
              f"kv_block_occupancy={m['kv_block_occupancy']}")


def bench_paged_capacity():
    """Paged vs contiguous KV capacity under a long/short mix: the same
    workload on a pool sized to ~22% of the contiguous stripes, plus the
    prefix-cache effect on repeated system prompts. Derived columns:
    block high-water mark (what the traffic really pinned) and prompt
    tokens served from the prefix cache instead of re-prefilled."""
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=2)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    system = list(rng.integers(1, cfg.vocab_size, 48))
    prompts = [list(rng.integers(1, cfg.vocab_size, 120))]
    prompts += [system + list(rng.integers(1, cfg.vocab_size, 8))
                for _ in range(6)]
    for n_blocks, tag in ((None, "parity"), (40, "paged40")):
        eng = Engine(params, cfg, batch=8, max_len=192, prefill_chunk=16,
                     block_size=16, n_blocks=n_blocks)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=[int(t) for t in p],
                               max_new_tokens=8), at_tick=2 * i)
        t0 = time.perf_counter()
        done = eng.run()
        dt = (time.perf_counter() - t0) * 1e6
        m = eng.metrics(done)
        print(f"serve_paged_capacity_{tag},{dt:.1f},"
              f"hbm_vs_contiguous={m['kv_hbm_vs_contiguous']};"
              f"kv_blocks_hwm={m['kv_blocks_hwm']}/{m['kv_blocks']};"
              f"prefix_hit_tokens={m['prefix_hit_tokens']};"
              f"prefix_hit_rate={m['prefix_hit_rate']}")


def bench_sched_slo():
    """Mixed-priority oversubscription at equal pool size: a burst of
    long best-effort prompts queued ahead of short deadline-tagged
    requests, on more requests than slots. Under fcfs the tagged
    requests head-of-line-block behind the long prefills; the slo
    policy (earliest-deadline-first) admits them first. Derived
    columns: p99 TTFT of the deadline-tagged subset (the SLO quantity),
    p99 TTFT of the whole mix, and preemption count — the acceptance
    bar is slo tagged-p99 strictly below fcfs tagged-p99."""
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request
    from repro.serving.metrics import percentile

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=2)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    longs = [list(rng.integers(1, cfg.vocab_size, 48)) for _ in range(6)]
    shorts = [list(rng.integers(1, cfg.vocab_size, 6)) for _ in range(4)]
    for sched in ("fcfs", "slo"):
        eng = Engine(params, cfg, batch=2, max_len=96, prefill_chunk=8,
                     block_size=16, n_blocks=16, scheduler=sched)
        rid = 0
        for p in longs:                       # best-effort bulk, queued first
            eng.submit(Request(rid=rid, prompt=[int(t) for t in p],
                               max_new_tokens=8))
            rid += 1
        for p in shorts:                      # urgent tail, queued behind
            eng.submit(Request(rid=rid, prompt=[int(t) for t in p],
                               max_new_tokens=8, deadline_ms=100.0))
            rid += 1
        t0 = time.perf_counter()
        done = eng.run()
        dt = (time.perf_counter() - t0) * 1e6
        tagged = [r.ttft_s for r in done if r.deadline_ms is not None]
        m = eng.metrics(done)
        print(f"serve_sched_{sched},{dt:.1f},"
              f"p99_ttft_tagged_s={percentile(tagged, 99):.4f};"
              f"p99_ttft_all_s={m['p99_ttft_s']};"
              f"preemptions={m['preemptions']}")


def bench_decode_megatick():
    """Fused multi-token decode megaticks: the same lockstep decode
    workload at decode_steps K in {1, 4, 8}. K=1 is the byte-identical
    single-step anchor (one jitted launch + a full (B, V) logits
    host round-trip per generated token); K>1 runs K steps per
    dispatch with sampling device-resident. Derived columns are
    STRUCTURAL, from the engine's own counters: dispatches per decode
    token (<= 1/K at steady state — the quantity the megatick cuts)
    and tokens per pure-decode dispatch; wall-clock tok/s rides along
    as fake-device context."""
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=2)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompts = [list(rng.integers(1, cfg.vocab_size, 8)) for _ in range(4)]
    for K in (1, 4, 8):
        eng = Engine(params, cfg, batch=4, max_len=128, prefill_chunk=8,
                     decode_steps=K)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=[int(t) for t in p],
                               max_new_tokens=33))
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        m = eng.metrics(done)
        dpt = m["decode_dispatches"] / max(m["decode_tokens"], 1)
        print(f"serve_megatick_K{K},{dt * 1e6:.1f},"
              f"tok_per_s={m['new_tokens'] / dt:.1f};"
              f"dispatches_per_decode_token={dpt:.4f};"
              f"tokens_per_dispatch={m['tokens_per_dispatch']}")


def _paged_bounded_setup(B, KVH, D, bs, n_blocks, max_blocks, live_blocks,
                         seed=3):
    """Pool + tables for the bounded-vs-masked comparison: every slot
    references ``live_blocks`` distinct blocks scattered over the pool
    (and therefore over the rank shards), lengths fill them exactly."""
    rng = np.random.default_rng(seed)
    blocks = rng.permutation(n_blocks)[:B * live_blocks]
    tables = np.full((B, max_blocks), -1, np.int32)
    tables[:, :live_blocks] = blocks.reshape(B, live_blocks)
    k = jax.random.normal(jax.random.PRNGKey(1), (n_blocks, bs, KVH, D),
                          jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (n_blocks, bs, KVH, D),
                          jnp.float32)
    cur = np.full((B,), live_blocks * bs, np.int32)
    return k, v, jnp.asarray(tables), jnp.asarray(cur)


def _paged_scored_positions(n_loc, bs, KVH, D, B, width, bounded):
    """STRUCTURAL per-slot work model: positions each slot scores per
    rank per step, derived from the implementation's own arrays — the
    bounded number is the position axis of the gather the fused region
    actually performs (jax.eval_shape on fd.gather_owned_blocks), the
    masked number is the flattened local pool shard."""
    if not bounded:
        return n_loc * bs
    view, _ = jax.eval_shape(
        fd.gather_owned_blocks,
        jax.ShapeDtypeStruct((n_loc, bs, KVH, D), jnp.float32),
        jax.ShapeDtypeStruct((B, width), jnp.int32), 0)
    return view.shape[1]


def bench_paged_bounded(W=8):
    """Tentpole bench: bounded table-gather vs masked-pool paged decode
    across pool sizings. The masked path's per-slot work scales with
    the pool shard (batch x the contiguous per-slot FLOPs at parity);
    the bounded path's is constant at gather_width x block_size,
    bounded by max_blocks x block_size whatever the pool size. The
    derived column carries the structural per-slot scored-position
    counts next to the (fake-device, structure-only) wall clock."""
    n = len(jax.devices())
    W = min(W, n)
    mesh = make_mesh((W,), ("model",))
    B, H, KVH, D = 8, 8, 4, 16
    bs, max_blocks, live = 8, 4, 2
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, D), jnp.float32)
    kn = jax.random.normal(jax.random.PRNGKey(4), (B, KVH, D), jnp.float32)
    vn = jax.random.normal(jax.random.PRNGKey(5), (B, KVH, D), jnp.float32)
    bound = max_blocks * bs
    gw = pow2_bucket(live, max_blocks)
    for n_blocks in (B * max_blocks // 2, B * max_blocks,
                     2 * B * max_blocks):     # oversub / parity / roomy
        n_blocks += (-n_blocks) % W
        n_loc = n_blocks // W
        k, v, tables, cur = _paged_bounded_setup(B, KVH, D, bs, n_blocks,
                                                 max_blocks, live)
        sh = NamedSharding(mesh, P("model", None, None, None))
        k_sh, v_sh = jax.device_put(k, sh), jax.device_put(v, sh)
        for bounded, tb in ((False, tables), (True, tables[:, :gw])):
            fn = jax.jit(lambda q, kn, vn, kp, vp, c, t, bd=bounded:
                         fd.decode_paged_attention_fused_sm(
                             q, kn, vn, kp, vp, c, t, mesh, scale=0.25,
                             mode="ring", bounded=bd)[0])
            us = timeit(fn, q, kn, vn, k_sh, v_sh, cur, tb, iters=10)
            scored = _paged_scored_positions(n_loc, bs, KVH, D, B,
                                             tb.shape[1], bounded)
            tag = "bounded" if bounded else "masked"
            print(f"paged_{tag}_pool{n_blocks},{us:.1f},"
                  f"per_slot_scored={scored};"
                  f"bound_max_blocks_x_bs={bound}")


def _bench_ci_megatick(K=4):
    """Megatick leg of the CI gate: a lockstep decode workload on a
    tiny smoke engine. STRUCTURAL — steady-state decode dispatches per
    token, counted from the engine's own counters (never wall-clock),
    must stay <= 1/K; the K=1 engine is run first and the streams must
    be token-identical so the gate cannot pass on a broken fused
    path. Returns the report fragment."""
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=1)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(1, cfg.vocab_size, 6)) for _ in range(4)]
    streams, counts = {}, None
    for k in (1, K):
        eng = Engine(params, cfg, batch=4, max_len=64, prefill_chunk=8,
                     decode_steps=k)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=[int(t) for t in p],
                               max_new_tokens=17))
        done = eng.run()
        streams[k] = {r.rid: tuple(r.out_tokens) for r in done}
        if k == K:
            counts = (eng.decode_dispatch_count, eng.decode_token_count)
    dpt = counts[0] / max(counts[1], 1)
    return {
        "megatick_check": "steady-state decode dispatches-per-token "
                          "<= 1/K",
        "megatick_ok": bool(dpt <= 1.0 / K
                            and streams[1] == streams[K]),
        "decode_steps": int(K),
        "megatick_decode_dispatches": int(counts[0]),
        "megatick_decode_tokens": int(counts[1]),
        "megatick_dispatches_per_token": round(dpt, 4),
        "megatick_bound": round(1.0 / K, 4),
        "megatick_tokens_match_single_step": bool(
            streams[1] == streams[K]),
    }


def _bench_ci_mixed(K=4):
    """Mixed-megatick leg of the CI gate: a STAGGERED-ARRIVAL open-loop
    workload — new prompts keep arriving while earlier slots decode, so
    prefill is in flight for most of the run and the pure-decode
    megatick alone cannot engage (the exact case the lockstep gate
    above cannot see). STRUCTURAL: the COMBINED decode
    dispatches-per-token (pure + mixed fused dispatches over all decode
    tokens) must stay <= 1/K, the mixed program must actually have
    carried prompt tokens, and the K-step streams must be
    token-identical to the single-step engine. Returns the report
    fragment."""
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=1)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(8)
    prompts = [list(rng.integers(1, cfg.vocab_size, 6)) for _ in range(8)]
    streams, counts, prompt_toks = {}, None, 0
    for k in (1, K):
        eng = Engine(params, cfg, batch=4, max_len=64, prefill_chunk=8,
                     decode_steps=k)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=[int(t) for t in p],
                               max_new_tokens=16), at_tick=2 * i)
        done = eng.run()
        streams[k] = {r.rid: tuple(r.out_tokens) for r in done}
        if k == K:
            counts = (eng.decode_dispatch_count
                      + eng.mixed_dispatch_count,
                      eng.decode_token_count
                      + eng.mixed_decode_token_count)
            prompt_toks = eng.mixed_prompt_token_count
    dpt = counts[0] / max(counts[1], 1)
    return {
        "mixed_check": "staggered-arrival (prefill in flight) combined "
                       "decode dispatches-per-token <= 1/K",
        "mixed_ok": bool(dpt <= 1.0 / K and prompt_toks > 0
                         and streams[1] == streams[K]),
        "mixed_dispatches_plus_decode": int(counts[0]),
        "mixed_plus_decode_tokens": int(counts[1]),
        "mixed_prompt_tokens": int(prompt_toks),
        "mixed_dispatches_per_token": round(dpt, 4),
        "mixed_bound": round(1.0 / K, 4),
        "mixed_tokens_match_single_step": bool(
            streams[1] == streams[K]),
    }


def _bench_ci_cancel(K=4):
    """Cancellation leg of the CI gate: an open-loop STAGGERED-ARRIVAL
    workload where two victims are aborted mid-stream (each after its
    first emitted token, exactly the serving front-end's hang-up /
    DELETE path). STRUCTURAL assertions, from the engine's own
    counters:

    * every SURVIVING stream is token-identical to a reference engine
      that never saw the victims — cancellation must not perturb
      co-batched slots (the token-identity invariant, proven by
      comparing streams, not wall-clock);
    * the COMBINED decode dispatches-per-token stays <= 1/K with the
      aborts in flight — cancellation must not degrade the megatick
      machinery back toward one dispatch per token;
    * the victims' blocks are actually freed
      (``blocks_freed_on_abort > 0``) and RE-ALLOCATABLE: a post-cancel
      admission must run to completion in the same pool.

    Returns the report fragment."""
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=1)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    prompts = [list(rng.integers(1, cfg.vocab_size, 6)) for _ in range(6)]
    victims = {1, 3}

    def make():
        return Engine(params, cfg, batch=4, max_len=64, prefill_chunk=8,
                      decode_steps=K, block_size=16, n_blocks=16)

    # reference: the survivors alone, same staggered arrival pattern —
    # token identity is scheduling-independent, so any schedule drift
    # from the missing victims must not change a single token
    ref = make()
    for i, p in enumerate(prompts):
        if i in victims:
            continue
        ref.submit(Request(rid=i, prompt=[int(t) for t in p],
                           max_new_tokens=16), at_tick=2 * i)
    ref_streams = {r.rid: tuple(r.out_tokens) for r in ref.run()}

    eng = make()
    reqs = []
    for i, p in enumerate(prompts):
        r = Request(rid=i, prompt=[int(t) for t in p], max_new_tokens=16)
        reqs.append(r)
        eng.submit(r, at_tick=2 * i)
    done, pending = [], set(victims)
    while eng.queue or eng.active:
        done += eng.tick()
        # abort each victim the first megatick it has streamed a token:
        # mid-stream, co-batched with live decodes
        for r in list(eng.active.values()):
            if r.rid in pending and r.out_tokens:
                eng.cancel(r.rid)
                pending.discard(r.rid)
    freed = eng.blocks_freed_on_abort
    # freed blocks must be re-allocatable: admit one more request into
    # the same pool and run it to completion
    extra = Request(rid=99, prompt=[int(t) for t in prompts[0]],
                    max_new_tokens=8)
    eng.submit(extra)
    done += eng.run()
    streams = {r.rid: tuple(r.out_tokens) for r in done
               if r.rid not in victims and r.rid != 99}
    counts = (eng.decode_dispatch_count + eng.mixed_dispatch_count,
              eng.decode_token_count + eng.mixed_decode_token_count)
    dpt = counts[0] / max(counts[1], 1)
    ok = bool(dpt <= 1.0 / K
              and streams == ref_streams
              and eng.cancel_count == len(victims)
              and freed > 0
              and len(extra.out_tokens) == 8)
    return {
        "cancel_check": "mid-stream aborts: survivors token-identical, "
                        "combined dispatches-per-token <= 1/K, freed "
                        "blocks re-allocatable",
        "cancel_ok": ok,
        "cancel_count": int(eng.cancel_count),
        "cancel_blocks_freed": int(freed),
        "cancel_dispatches_per_token": round(dpt, 4),
        "cancel_bound": round(1.0 / K, 4),
        "cancel_survivors_match_reference": bool(streams == ref_streams),
        "cancel_readmit_tokens": int(len(extra.out_tokens)),
    }


def _bench_ci_chaos(K=4):
    """Chaos leg of the CI gate (gate 5, PR 10): the seeded fault plan
    from docs/robustness.md — one poisoned slot, one transient
    dispatch failure, one pool-exhaustion spike — against a staggered
    open-loop workload, then a mid-flight drain->snapshot->restore
    into a FRESH engine. STRUCTURAL assertions:

    * every SURVIVING stream (everything but the poisoned victim) is
      token-identical to a fault-free reference run — recovery must be
      invisible to co-batched requests;
    * the COMBINED dispatches-per-decode-token, counting every retry
      dispatch and both engines (pre-drain + restored), stays <= 1/K —
      fault handling must not degrade the megatick machinery;
    * the restore resumes EVERY request unfinished at the snapshot,
      and each one that had streamed tokens resumes as a PREFIX HIT
      (its already-computed KV is served, not recomputed).

    Returns the report fragment."""
    import tempfile

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request
    from repro.serving.faults import FaultPlan, FaultSpec

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=1)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(13)
    prompts = [list(rng.integers(1, cfg.vocab_size, 6)) for _ in range(4)]
    victim_rid = 1                 # FCFS: rid 1 lands in slot 1

    def make(plan=None):
        return Engine(params, cfg, batch=4, max_len=64, prefill_chunk=8,
                      decode_steps=K, block_size=8, n_blocks=24,
                      fault_plan=plan)

    def submit_all(eng):
        reqs = [Request(rid=i, prompt=[int(t) for t in p],
                        max_new_tokens=16)
                for i, p in enumerate(prompts)]
        for i, r in enumerate(reqs):
            eng.submit(r, at_tick=2 * i)
        return reqs

    # fault-free reference streams
    ref = make()
    submit_all(ref)
    ref_streams = {r.rid: tuple(r.out_tokens) for r in ref.run()}

    # the seeded plan: transient dispatch failure at tick 3, poisoned
    # logits on the victim's slot at tick 4, pool spike over ticks 5-6
    plan = FaultPlan([
        FaultSpec("dispatch", tick=3, count=1),
        FaultSpec("tokens", tick=4, slot=1),
        FaultSpec("pool", tick=5, blocks=4, hold_ticks=2),
    ])
    eng = make(plan)
    reqs = submit_all(eng)
    done = []
    for _ in range(6):             # all three faults fire in here
        done += eng.tick()
    streamed_at_snap = {r.rid for r in reqs
                        if r.out_tokens and not r.done}
    unfinished = {r.rid for r in reqs if not r.done}
    with tempfile.TemporaryDirectory() as tmp:
        step = eng.snapshot(Checkpointer(tmp))
        fresh = make()
        restored = fresh.restore(Checkpointer(tmp), step)
        done += fresh.run()
    by_rid = {r.rid: r for r in done}
    survivors = {r.rid: tuple(r.out_tokens) for r in done
                 if r.rid != victim_rid}
    expect = {rid: s for rid, s in ref_streams.items()
              if rid != victim_rid}
    victim = next(r for r in reqs if r.rid == victim_rid)
    dispatches = (eng.decode_dispatch_count + eng.mixed_dispatch_count
                  + eng.dispatch_retry_count
                  + fresh.decode_dispatch_count
                  + fresh.mixed_dispatch_count
                  + fresh.dispatch_retry_count)
    tokens = (eng.decode_token_count + eng.mixed_decode_token_count
              + fresh.decode_token_count + fresh.mixed_decode_token_count)
    dpt = dispatches / max(tokens, 1)
    resumed = {r.rid for r in restored}
    prefix_ok = all(by_rid[rid].reused_tokens > 0
                    for rid in streamed_at_snap)
    ok = bool(dpt <= 1.0 / K
              and survivors == expect
              and victim.finish_reason == "error"
              and resumed == unfinished
              and all(by_rid[rid].done for rid in resumed)
              and prefix_ok
              and plan.injected == 3)
    return {
        "chaos_check": "seeded faults (poison+dispatch+pool spike) + "
                       "drain/restore: survivors token-identical, "
                       "combined dispatches-per-token <= 1/K, resumed "
                       "requests are prefix hits",
        "chaos_ok": ok,
        "chaos_faults_injected": int(plan.injected),
        "chaos_dispatch_retries": int(eng.dispatch_retry_count),
        "chaos_victim_finish_reason": victim.finish_reason,
        "chaos_dispatches_per_token": round(dpt, 4),
        "chaos_bound": round(1.0 / K, 4),
        "chaos_survivors_match_reference": bool(survivors == expect),
        "chaos_resumed": sorted(resumed),
        "chaos_resume_prefix_hits": bool(prefix_ok),
    }


def bench_mixed_megatick():
    """Mixed prefill+decode megaticks under staggered arrivals: the
    open-loop steady state where PR 5's pure megaticks bailed out to
    one dispatch per token. K=1 is the single-step anchor; K>1 runs
    the fused mixed program (``lm.decode_mixed``) whenever prefill is
    in flight. Derived columns are STRUCTURAL, from the engine's own
    counters: combined decode dispatches-per-token (pure + mixed) and
    the prompt-vs-decode token split of the mixed dispatches."""
    from repro.configs import get_config, smoke_config
    from repro.models import lm as lm_mod
    from repro.serving.engine import Engine, Request

    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=2)
    params = lm_mod.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(1, cfg.vocab_size, 8)) for _ in range(8)]
    for K in (1, 4, 8):
        eng = Engine(params, cfg, batch=4, max_len=128, prefill_chunk=8,
                     decode_steps=K)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=[int(t) for t in p],
                               max_new_tokens=33), at_tick=3 * i)
        t0 = time.perf_counter()
        done = eng.run()
        dt = time.perf_counter() - t0
        m = eng.metrics(done)
        print(f"serve_mixed_megatick_K{K},{dt * 1e6:.1f},"
              f"tok_per_s={m['new_tokens'] / dt:.1f};"
              f"combined_dispatches_per_decode_token="
              f"{m['decode_dispatches_per_token']};"
              f"mixed_dispatches={m['mixed_dispatches']};"
              f"mixed_prompt_tokens={m['mixed_prompt_tokens']};"
              f"mixed_decode_tokens={m['mixed_decode_tokens']}")


def bench_ci(out_path="BENCH_ci.json"):
    """Per-PR CI perf gate (bench-smoke job): tiny interpret-friendly
    shapes, STRUCTURAL assertions only, so CPU runners stay
    deterministic; wall-clock goes into the JSON as context.

    Gate 1 (paged bounded): the bounded path's modeled per-slot work
    (the position axis of the gather it actually performs) must stay
    <= max_blocks x block_size, with bounded == masked numerically
    (rtol 1e-5) so the gate cannot pass on a broken kernel.

    Gate 2 (decode megaticks): steady-state decode dispatches-per-token
    <= 1/K, counted from the engine's own counters, with the K-step
    streams token-identical to the single-step engine.

    Gate 3 (mixed megaticks): the same 1/K bound under a
    STAGGERED-ARRIVAL open-loop workload — prefill always in flight,
    the case gate 2 cannot see — from the COMBINED pure+mixed
    counters, with prompt tokens actually carried by the fused mixed
    program and streams token-identical to the single-step engine.

    Gate 4 (cancellation): mid-stream aborts under open-loop staggered
    arrivals — survivors token-identical to a victim-free reference,
    combined dispatches-per-token <= 1/K with aborts in flight, and
    the victims' freed blocks re-allocatable by a post-cancel
    admission.

    Gate 5 (chaos): the seeded fault plan — poisoned slot + transient
    dispatch failure + pool spike — then drain->snapshot->restore into
    a fresh engine: survivors token-identical to a fault-free
    reference, combined dispatches-per-token (retries included, both
    engines) <= 1/K, and every resumed request a prefix hit.

    Writes BENCH_ci.json and exits nonzero on any violation."""
    n = len(jax.devices())
    W = min(4, n)
    mesh = make_mesh((W,), ("model",))
    B, H, KVH, D = 4, 8, 4, 16
    bs, max_blocks, live = 8, 4, 2
    n_blocks = B * max_blocks
    n_blocks += (-n_blocks) % W
    n_loc = n_blocks // W
    gw = pow2_bucket(live, max_blocks)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, D), jnp.float32)
    kn = jax.random.normal(jax.random.PRNGKey(4), (B, KVH, D), jnp.float32)
    vn = jax.random.normal(jax.random.PRNGKey(5), (B, KVH, D), jnp.float32)
    k, v, tables, cur = _paged_bounded_setup(B, KVH, D, bs, n_blocks,
                                             max_blocks, live)
    sh = NamedSharding(mesh, P("model", None, None, None))
    k_sh, v_sh = jax.device_put(k, sh), jax.device_put(v, sh)
    res, times = {}, {}
    for bounded, tb in ((False, tables), (True, tables[:, :gw])):
        fn = jax.jit(lambda q, kn, vn, kp, vp, c, t, bd=bounded:
                     fd.decode_paged_attention_fused_sm(
                         q, kn, vn, kp, vp, c, t, mesh, scale=0.25,
                         mode="ring", bounded=bd)[0])
        tag = "bounded" if bounded else "masked"
        times[tag] = timeit(fn, q, kn, vn, k_sh, v_sh, cur, tb,
                            iters=3, warmup=1)
        res[tag] = np.asarray(fn(q, kn, vn, k_sh, v_sh, cur, tb))
    np.testing.assert_allclose(res["bounded"], res["masked"],
                               rtol=1e-5, atol=1e-5)
    bound = max_blocks * bs
    scored_b = _paged_scored_positions(n_loc, bs, KVH, D, B, gw, True)
    scored_m = _paged_scored_positions(n_loc, bs, KVH, D, B,
                                       tables.shape[1], False)
    report = {
        "check": "paged-bounded per-slot work <= max_blocks*block_size",
        "ok": bool(scored_b <= bound),
        **_bench_ci_megatick(),
        **_bench_ci_mixed(),
        **_bench_ci_cancel(),
        **_bench_ci_chaos(),
        "bounded_per_slot_scored": int(scored_b),
        "masked_per_slot_scored": int(scored_m),
        "bound_max_blocks_x_block_size": int(bound),
        "gather_width": int(gw),
        "block_size": int(bs),
        "max_blocks": int(max_blocks),
        "n_blocks": int(n_blocks),
        "devices": int(W),
        "bounded_us": round(times["bounded"], 1),
        "masked_us": round(times["masked"], 1),
        "outputs_match": True,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"bench_ci,{times['bounded']:.1f},"
          f"per_slot_scored={scored_b};bound={bound};ok={report['ok']};"
          f"megatick_dpt={report['megatick_dispatches_per_token']};"
          f"megatick_ok={report['megatick_ok']};"
          f"mixed_dpt={report['mixed_dispatches_per_token']};"
          f"mixed_ok={report['mixed_ok']};"
          f"cancel_dpt={report['cancel_dispatches_per_token']};"
          f"cancel_ok={report['cancel_ok']};"
          f"chaos_dpt={report['chaos_dispatches_per_token']};"
          f"chaos_ok={report['chaos_ok']}")
    if not report["ok"]:
        sys.exit(f"paged-bounded per-slot work {scored_b} exceeds "
                 f"bound {bound}")
    if not report["megatick_ok"]:
        sys.exit(
            f"megatick gate: dispatches-per-token "
            f"{report['megatick_dispatches_per_token']} vs bound "
            f"{report['megatick_bound']}, tokens_match="
            f"{report['megatick_tokens_match_single_step']}")
    if not report["mixed_ok"]:
        sys.exit(
            f"mixed-megatick gate: combined dispatches-per-token "
            f"{report['mixed_dispatches_per_token']} vs bound "
            f"{report['mixed_bound']}, prompt_tokens="
            f"{report['mixed_prompt_tokens']}, tokens_match="
            f"{report['mixed_tokens_match_single_step']}")
    if not report["cancel_ok"]:
        sys.exit(
            f"cancellation gate: dispatches-per-token "
            f"{report['cancel_dispatches_per_token']} vs bound "
            f"{report['cancel_bound']}, survivors_match="
            f"{report['cancel_survivors_match_reference']}, "
            f"cancels={report['cancel_count']}, "
            f"blocks_freed={report['cancel_blocks_freed']}, "
            f"readmit_tokens={report['cancel_readmit_tokens']}")
    if not report["chaos_ok"]:
        sys.exit(
            f"chaos gate: dispatches-per-token "
            f"{report['chaos_dispatches_per_token']} vs bound "
            f"{report['chaos_bound']}, survivors_match="
            f"{report['chaos_survivors_match_reference']}, "
            f"victim_finish={report['chaos_victim_finish_reason']}, "
            f"resumed={report['chaos_resumed']}, prefix_hits="
            f"{report['chaos_resume_prefix_hits']}, faults="
            f"{report['chaos_faults_injected']}")


def bench_pallas_ag_gemm(W=4):
    """Fused in-kernel AG+GEMM (interpret mode: structural check only)."""
    mesh = make_mesh((W,), ("model",))
    M, K, N = 64, 256, 512
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    a_sh = jax.device_put(a, NamedSharding(mesh, P(None, "model")))
    fn = jax.jit(lambda a, b: ops.ag_gemm(a, b, mesh, bn=128))
    us = timeit(fn, a_sh, b, iters=3, warmup=1)
    print(f"pallas_ag_gemm_fused_interp,{us:.1f},interpret_mode=1")


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "ag_gemm"):
        bench_ag_gemm()
    if which in ("all", "flash_decode"):
        bench_flash_decode()
    if which in ("all", "scaling"):
        bench_scaling()
    if which in ("all", "serving"):
        bench_serving_engine()
    if which in ("all", "megatick"):
        bench_decode_megatick()
        bench_mixed_megatick()
    if which in ("all", "paged"):
        bench_paged_capacity()
    if which in ("all", "bounded"):
        bench_paged_bounded()
    if which in ("all", "sched"):
        bench_sched_slo()
    if which in ("all", "pallas"):
        bench_pallas_ag_gemm()
    if which == "ci":
        # per-PR bench-smoke gate: structural per-slot work bound +
        # bounded==masked numeric identity + megatick dispatches-per-
        # token bound with K==1 token identity; writes BENCH_ci.json
        bench_ci()
