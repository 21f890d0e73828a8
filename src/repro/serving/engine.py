"""Continuous-batching serving engine: per-slot prefill + decode.

The decode step is where the paper's Flash Decode lives: one jitted
step runs the whole active batch against the block-sharded paged KV
pool, with the partial-softmax combine executed by the configured
fusion mode (auto / bsp / ring).

This is TRUE per-slot continuous batching over PAGED KV: the jitted
state carries a (B,) position vector and a (B, max_blocks) block table
(``repro.models.lm.init_paged_decode_state``), so every slot advances
independently and grows its cache one block at a time instead of
pinning a contiguous ``max_len`` stripe. A request can be admitted into
a freed slot at ANY tick; if its prompt prefix is resident in the
prefix cache, admission seeds the slot's table with the shared blocks
and prefill starts at the first novel token.

Scheduling per tick:

1. admit queued requests (whose arrival tick has passed) in the order
   the configured policy chooses, while the pool has a free slot AND
   enough blocks for the prompt + one generated token
   (block-availability admission); admission stops at the first
   request the pool cannot back, so long prompts are never starved by
   skip-ahead;
2. build a (B, C) token block: prefilling slots take their next
   ``min(C, remaining)`` prompt tokens (chunked batched prefill — one
   jitted call consumes the whole chunk via ``lm.decode_chunk``),
   decoding slots take their last sampled token (count 1), idle slots
   count 0. Counts are clamped to what the pool can actually back with
   blocks this tick (allocating/copy-on-writing at chunk boundaries) —
   a slot that cannot get a block stalls instead of corrupting memory.
   If EVERY active slot stalls, the policy names a victim and the
   engine preempts it instead of deadlocking (see below);
3. one jitted step; sample next tokens from each slot's last-consumed-
   token logits — greedy, or seeded per-request temperature/top-k
   (``sampler="temperature"``); retire finished requests and free their
   slots (private blocks return to the free list, registered prefix
   blocks stay resident for future hits). Sliding-window archs also
   reclaim each slot's dead blocks (positions rolled permanently out of
   the window) so rolling workloads stop pinning memory.

MEGATICKS (``decode_steps=K``, default 1): the per-token loop above
re-levies two of the paper's taxes at token granularity — one jitted
launch per generated token, plus a bulk host<->device barrier that
ships full (B, V) logits down and the sampled token back up every
tick. A K-step engine instead fuses many steps into ONE jitted program
with sampling DEVICE-RESIDENT, in one of two shapes:

* PURE megatick (``lm.decode_multi``, when no slot is prefilling):
  K decode steps in one scan — each step's sampled token feeds the
  next step in-graph, and only (B, K) token ids return to host.
* MIXED megatick (``lm.decode_mixed``, whenever any slot is
  prefilling): chunked-prefill PIGGYBACKING, Sarathi/vLLM-style. Each
  slot carries a per-step ROLE inside the same scan: steps below its
  prompt watermark consume the next prompt token from a host-provided
  (B, S) buffer; later steps feed back the sampled carry; steps past
  its budget freeze under the active mask. A slot that consumes its
  LAST prompt token at step j samples its FIRST generated token at
  step j — in the same dispatch — so prefill→decode transitions are
  token-identical to the unfused path and TTFT never waits for a
  megatick boundary. Decode-only slots run their K steps alongside, so
  one long prompt no longer degrades the whole batch back to one
  dispatch per token.

Megatick semantics (both shapes):

* one megatick is ONE scheduler tick and ONE dispatch — admission,
  arrival ticks, preemption checks, prefix registration, and
  sliding-window reclaim all happen at megatick BOUNDARIES;
* every slot gets a per-megatick token budget. Pure decode:
  ``min(K, remaining max_new_tokens, max_len headroom, blocks the
  pool can reserve)``. Mixed: a per-slot quota of
  ``megatick_token_budget`` tokens (default
  ``max(decode_steps, prefill_chunk)``) is split prefill-first —
  prompt tokens take ``min(quota, remaining prompt)``, and decode
  steps piggyback only if the prompt completes within the quota
  (capped at K and at the leftover quota). ``CachePool.reserve``
  pre-allocates the blocks the WHOLE megatick will write, prompt and
  decode together; a short reservation shrinks the prefill span first.
  A slot that exhausts its budget at step j freezes byte-identically
  for the remaining steps, exactly like an inactive slot today. If
  every slot's budget is 0, the engine preempts the policy's victim,
  as the single-step path does;
* the scan length is bucketed to the next power of two (clamped at K,
  or at the token quota for mixed ticks) and threaded as a STATIC jit
  arg like ``gather_width``, so ragged tail megaticks don't pay the
  full length while compiles stay bounded at log2;
* sampling in-scan uses the same (seed, rid, token-index)-folded keys
  as the host path — mixed ticks index by ``steps0 + j - emit_from``
  so a slot's n-th generated token uses the n-th key no matter which
  step emitted it — so sampled streams stay scheduling-independent
  and preemption-safe; greedy engines argmax in-graph;
* TTFT is unaffected (a request's first token is emitted by the tick
  that completes its prefill — in mixed mode that is the very step
  that consumed the last prompt token); TPOT and ``finished_t`` stamp
  at megatick boundaries, so sub-megatick inter-token times are
  averaged over the tokens of the batch that produced them.

``decode_steps=1`` is the regression anchor: it takes the exact
single-step code path, byte-identical to the pre-megatick engine
(pinned tick/dispatch counts). The ``tokens_per_dispatch`` metric and
the ``decode_dispatches``/``decode_tokens`` counters expose the win
structurally, and the ``mixed_dispatches``/``mixed_prompt_tokens``/
``mixed_decode_tokens`` counters extend it to continuous arrivals:
``decode_dispatches_per_token`` (pure + mixed dispatches over all
decode tokens) stays <= 1/K at steady state even with prefill
permanently in flight (the CI bench gates assert this from the
counters, not wall-clock).

Scheduling POLICY is pluggable (``scheduler=`` — a name or a
``repro.serving.scheduler.SchedulerPolicy`` instance; CLI flag
``--scheduler`` on ``repro.launch.serve`` and
``examples/serve_decode.py``):

* ``fcfs``     (default) — submission order; admission decisions are
  byte-identical to the pre-policy engine (regression-anchored: same
  token streams, same tick/dispatch counts).
* ``priority`` — per-request ``Request.priority`` (higher first) with
  aging, so sustained high-priority traffic cannot starve the
  low-priority tail forever.
* ``slo``      — earliest-deadline-first on ``Request.deadline_ms``
  (a TTFT target relative to submission; ``--deadline-ms``); untagged
  requests run FIFO after every deadline-tagged one.

The policy interface is three host-side hooks —
``select_admissions(queue, pool, tick)``,
``select_victim(active, pool)``, ``on_tick_end(queue, active, tick)``
— documented in ``repro.serving.scheduler``.

PREEMPTION replaces the old pool-exhaustion ``RuntimeError``: when all
active slots stall on block availability, the victim's private blocks
are freed (``CachePool.preempt`` — its fully-written chunks are first
registered as prefix blocks, so resuming is a prefix hit that skips
re-prefilling them) and the request re-queues with its generated tokens
folded into an effective prompt. Decode logits depend only on the token
history, so a preempted request's output stream is token-identical to
an uninterrupted run — for greedy sampling and for the seeded
temperature sampler, whose PRNG keys fold (seed, rid, token index) and
therefore survive rescheduling. The engine still raises when preemption
cannot make progress (a single request's history has outgrown the whole
pool).

CANCELLATION (``Engine.cancel(rid)``) is the abort half of the serving
story: a request whose USER went away (hang-up, timeout) leaves
mid-stream instead of decoding to completion. Queued requests just
leave the queue; active ones take ``CachePool.abort`` — their written
prompt chunks register as prefix blocks (still LRU-resident for future
identical prompts), every block reference drops, and the freed blocks
are immediately re-allocatable. Cancellation is applied BETWEEN
dispatches (the async front-end, ``repro.launch.server``, applies it
at megatick boundaries), so surviving co-batched streams are never
perturbed — token-identical to solo runs, with the combined 1/K
dispatch bound still holding (BENCH_ci gate 4 asserts both with aborts
in flight).

ROBUSTNESS (docs/robustness.md): the engine carries a deterministic
fault-injection plane (``fault_plan=`` — a seeded
``repro.serving.faults.FaultPlan`` keyed by (tick, site)) and the
recovery machinery it exercises. Transient dispatch failures retry
with bounded deterministic backoff (``DISPATCH_ATTEMPTS`` total
attempts — a static trip count, so the TAX003 dispatch budgets stay
provable); pool state commits only on success, so retries replay
identical inputs. A NaN/Inf guard validates every sampled id read
back from the device: a poisoned slot retires through the
``CachePool.abort`` path with ``finish_reason="error"`` and only its
pre-poison history registered, while co-batched survivors stay
token-identical to a fault-free run. A monotonic-clock
``StragglerWatchdog`` times every megatick, and an optional
``DegradedModeController`` ladder (``degraded=True``) steps the
engine down under sustained pressure — halve K, then K=1 +
``bounded_gather=False`` (rebuilding the jitted closures), then shed
intake — and back up after sustained health; every rung is
token-identical by the gated K-/gather-variation invariants.
``drain()`` parks all in-flight work at a clean boundary via the
preemption path; ``snapshot()``/``restore()`` round-trip the full
serving state through ``checkpoint.Checkpointer`` so a killed server
resumes every unfinished request as a prefix hit (BENCH_ci gate 5
asserts survivor identity, the 1/K bound with faults in flight, and
the drain→restore prefix-hit resume).

Per-request metrics: TTFT (submit -> first generated token) and TPOT
(mean inter-token time over the generated tokens); engine metrics add
p50/p99 latency tails, preemption/reclaim counters, and block
occupancy + prefix-hit counters.

Tracing (``serving.metrics.Spans``): every tick is an ``engine.tick``
host span enclosing, in order, ``engine.admit`` (with the admitted
rids), ``engine.prepare`` (reservations and host inputs),
``engine.upload`` (``CachePool.sync``), ``engine.dispatch`` (the
enqueue of the jitted program, retries included), ``engine.readback``
(the one designed wait for the device) and ``engine.retire``; each
span's seconds accumulate in ``engine_<phase>_s``. Beside them,
``queue_wait_s``/``admissions`` time each request from arrival to its
first admission, and ``scan_slot_steps``/``scan_slot_capacity`` count
the slot-steps the dispatched programs did work in against the
slot-steps they were compiled for (scan length x batch). The jitted
programs carry the names ``megatick_mixed``, ``megatick_decode``,
``step_single`` and ``step_chunk``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flash_decode import paged_view
from repro.distributed import context as dctx
from repro.distributed.fault_tolerance import StragglerWatchdog
from repro.models import lm
from repro.serving import sampler as sampler_lib
from repro.serving.faults import (DISPATCH_ATTEMPTS, DegradedModeController,
                                  DispatchFailedError, FaultPlan,
                                  TransientDispatchError, backoff_s)
from repro.serving.kv_cache import CachePool, pow2_bucket
from repro.serving.metrics import Spans, latency_metrics, request_latency
from repro.serving.scheduler import SchedulerPolicy, get_scheduler

# the host spans of one tick: ``engine.tick`` encloses the rest, in order
TICK_SPANS = ("engine.tick", "engine.admit", "engine.prepare",
              "engine.upload", "engine.dispatch", "engine.readback",
              "engine.retire")

@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    arrival_tick: int = 0            # earliest tick it may be admitted
    temp: float = 1.0                # per-request sampling temperature
    top_k: int = 0                   # per-request top-k (0 = full vocab)
    priority: int = 0                # higher = sooner ("priority" policy)
    deadline_ms: float | None = None  # TTFT target ("slo" policy)
    out_tokens: list = dataclasses.field(default_factory=list)
    slot: int = -1
    consumed: int = 0                # effective-prompt tokens written
    reused_tokens: int = 0           # prompt tokens served by a prefix hit
    preemptions: int = 0             # times evicted and re-queued
    seq: int = 0                     # submission order (engine-stamped)
    done: bool = False
    cancelled: bool = False          # aborted mid-stream (Engine.cancel)
    finish_reason: str | None = None  # "length" | "cancelled" | "error"
    error: str | None = None         # human-readable poison/fault reason
    submitted_t: float = 0.0
    arrived_t: float = 0.0           # monotonic: reached the system
    admitted_t: float = 0.0
    first_token_t: float = 0.0
    finished_t: float = 0.0

    def __post_init__(self):
        # what a (re)admission actually prefills: the original prompt,
        # plus — after a preemption — the tokens generated before
        # eviction (decode logits depend only on the token history, so
        # replaying prompt+generated resumes the stream exactly)
        self.eff_prompt: list[int] = list(self.prompt)

    @property
    def prefilling(self) -> bool:
        return self.consumed < len(self.eff_prompt)

    @property
    def ttft_s(self) -> float:
        """Time to first token (submit -> first generated token)."""
        return max(self.first_token_t - self.submitted_t, 0.0)

    @property
    def tpot_s(self) -> float:
        """Mean time per output token after the first. 0.0 until the
        request finishes — before ``finished_t`` is stamped there is no
        meaningful interval to average."""
        n = len(self.out_tokens)
        if n <= 1 or self.finished_t == 0.0:
            return 0.0
        return max(self.finished_t - self.first_token_t, 0.0) / (n - 1)


class Engine:
    """Continuous-batching scheduler over a paged ``CachePool``.

    ``prefill_chunk`` — max prompt tokens a slot consumes per tick. 1
    degrades to token-at-a-time prefill; larger values amortize
    dispatch overhead and shorten TTFT under load.

    ``sampler`` — "greedy" (PR-1-identical argmax) or "temperature"
    (seeded per-request temperature/top-k via ``Request.temp`` /
    ``Request.top_k``; a request with ``temp=0`` is greedy). The PRNG
    stream is keyed on (seed, request id, token index), so a request's
    sampled tokens are reproducible regardless of scheduling — and
    survive preemption.

    ``scheduler`` — admission/preemption policy: "fcfs" (default,
    regression-anchored), "priority", "slo", or a
    ``repro.serving.scheduler.SchedulerPolicy`` instance (e.g.
    ``PriorityScheduler(aging_ticks=8)``).

    ``block_size`` / ``n_blocks`` — paged-KV granularity and pool size;
    ``n_blocks=None`` defaults to contiguous parity (batch * max_len
    worth). Size it below parity to serve mixed-length traffic in a
    fraction of the HBM; exhaustion under oversubscription preempts
    instead of failing.

    ``decode_steps`` — decode megatick length K: one jitted dispatch
    runs K decode steps with sampling device-resident, returning token
    ids instead of K full logit tensors. Pure-decode batches take
    ``lm.decode_multi``; batches with prefill in flight take the fused
    MIXED program (``lm.decode_mixed``), where prompt chunks piggyback
    on the same scan. 1 (default) keeps the byte-identical single-step
    path; larger K cuts steady-state decode to <= 1/K dispatches per
    token while staying token-identical (budgets freeze slots that
    finish mid-megatick; preemption and sliding-window reclaim move to
    megatick boundaries).

    ``megatick_token_budget`` — per-slot token quota M of a MIXED
    megatick (prompt tokens consumed + decode steps piggybacked per
    slot per dispatch). Default ``max(decode_steps, prefill_chunk)``;
    must be >= ``decode_steps`` so a decode-only slot can still run
    its full K steps (else the 1/K dispatch bound cannot hold). Larger
    M drains long prompts in fewer dispatches at the cost of more
    work per dispatch (chunked-prefill knob, Sarathi-style).

    ``bounded_gather`` — distributed paged attention gathers each slot's
    referenced blocks through its table before scoring (per-slot work
    bounded at gather_width x block_size; the width tracks the pool's
    live ``max_blocks_in_use`` watermark in power-of-two buckets, so
    jitted-step recompiles stay bounded at log2(max_blocks)). ``False``
    keeps the masked whole-pool-shard path — the token-identity oracle
    the battery checks the bounded path against.
    """

    def __init__(self, params, cfg, *, batch: int = 8, max_len: int = 512,
                 prefill_chunk: int = 8, sampler: str = "greedy",
                 seed: int = 0, block_size: int = 16,
                 n_blocks: int | None = None,
                 scheduler: str | SchedulerPolicy = "fcfs",
                 decode_steps: int = 1,
                 megatick_token_budget: int | None = None,
                 bounded_gather: bool = True,
                 fault_plan: FaultPlan | None = None,
                 watchdog: StragglerWatchdog | None = None,
                 degraded: DegradedModeController | bool | None = None,
                 retry_backoff_s: float = 0.02,
                 retry_backoff_cap_s: float = 0.5):
        if sampler not in ("greedy", "temperature"):
            raise ValueError(f"unknown sampler {sampler!r}: "
                             f"expected 'greedy' or 'temperature'")
        if decode_steps < 1:
            raise ValueError(f"decode_steps must be >= 1, "
                             f"got {decode_steps}")
        if (megatick_token_budget is not None
                and megatick_token_budget < decode_steps):
            raise ValueError(
                f"megatick_token_budget {megatick_token_budget} < "
                f"decode_steps {decode_steps}: the per-slot quota must "
                f"at least cover a full decode megatick, or the 1/K "
                f"dispatch bound cannot hold")
        self.policy = get_scheduler(scheduler)   # fail fast, pre-pool-init
        # the DistContext (mesh, rules, fusion mode) current at
        # construction: the pool is laid out by it, and every program
        # the engine builds is traced under it, whatever is current then
        self.ctx = dctx.current()
        self.params = params
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.queue: deque[Request] = deque()
        self.active: dict[int, Request] = {}   # slot -> request
        self.pool = CachePool(params, cfg, batch, max_len,
                              block_size=block_size, n_blocks=n_blocks)
        self.sampler = sampler
        self.seed = int(seed)
        self._base_key = jax.random.PRNGKey(seed)
        self.decode_steps = int(decode_steps)
        self.megatick_tokens = (int(megatick_token_budget)
                                if megatick_token_budget is not None
                                else max(self.decode_steps,
                                         self.prefill_chunk))
        self.tick_count = 0
        self.dispatch_count = 0     # ticks that actually ran a jitted step
        self.preempt_count = 0      # victims evicted on pool exhaustion
        self.cancel_count = 0       # requests aborted via Engine.cancel
        self.blocks_freed_on_abort = 0   # blocks aborts made re-allocatable
        # decode-phase structural counters (the megatick win): dispatches
        # where every participating slot was decoding, and the tokens
        # those dispatches produced — dispatches-per-token is their ratio
        self.decode_dispatch_count = 0
        self.decode_token_count = 0
        # mixed-megatick counters: fused dispatches that carried prompt
        # chunks alongside (or instead of) decode steps, split into the
        # prompt tokens consumed and the decode tokens emitted — with
        # these, dispatches-per-decode-token stays measurable under
        # continuous arrivals (prefill always in flight), where the
        # pure-decode counters above never fire
        self.mixed_dispatch_count = 0
        self.mixed_prompt_token_count = 0
        self.mixed_decode_token_count = 0
        # dispatches whose paged attention reads the whole pool, not a
        # per-slot gather (flash_decode.paged_view)
        self.pool_view_dispatch_count = 0
        self._seq = 0               # submission order stamp
        self.bounded_gather = bool(bounded_gather)
        # -------- robustness plane (docs/robustness.md) --------------
        # faults: a deterministic FaultPlan keyed by (tick, site); ticks
        # are 1-based — a spec with tick=t fires during the t-th tick()
        self.faults = fault_plan
        self.watchdog = (watchdog if watchdog is not None
                         else StragglerWatchdog())
        if degraded is True:
            degraded = DegradedModeController()
        self.degraded = degraded or None
        self.retry_backoff_s = float(retry_backoff_s)
        self.retry_backoff_cap_s = float(retry_backoff_cap_s)
        self._cfg_bounded = bool(bounded_gather)  # configured gather mode
        self._spike_until = None    # tick the seized pool blocks return
        self.dispatch_retry_count = 0    # retried megatick dispatches
        self.dispatch_failure_count = 0  # retry budgets exhausted
        self.error_count = 0             # slots retired finish_reason=error
        self.slow_tick_count = 0         # watchdog-flagged megaticks
        self.drain_count = 0             # requests parked by drain()
        # -------- tracing: host spans of each tick and their seconds,
        # queue wait to first admission, and scan fill (slot-steps that
        # did work against scan length x batch, over every dispatch)
        self.spans = Spans(*TICK_SPANS)
        self.queue_wait_s = 0.0
        self.admission_count = 0
        self.scan_slot_steps = 0
        self.scan_slot_capacity = 0
        self._build_dispatchers()

    def _count_dispatch(self, gw: int):
        """Count one dispatch at static gather width ``gw``, and whether
        its paged attention reads the whole pool (``paged_view``)."""
        self.dispatch_count += 1
        if not self.cfg.is_attention_free and paged_view(
                self.ctx.model_axis_size, self.batch, gw,
                self.pool.n_blocks) == "pool":
            self.pool_view_dispatch_count += 1

    def _build_dispatchers(self):
        """(Re)build the jitted dispatch closures. They capture
        ``bounded_gather`` at closure-construction time, so the
        degraded-mode fallback to the masked-pool oracle path
        (level >= 2) rebuilds them instead of mutating a flag the
        compiled programs can no longer see."""
        cfg = self.cfg
        sampler = self.sampler
        ctx = self.ctx

        def under_ctx(fn, name):
            def traced(*args):
                with dctx.use(ctx):
                    return fn(*args)
            # the program's name: jit(<name>) in its HLO module and on
            # a trace's XLA Modules line
            traced.__name__ = traced.__qualname__ = name
            return traced

        # two jitted paths sharing the pool state: a 1-token step for
        # all-decoding ticks, a C-token scan when any slot is prefilling.
        # gw is the STATIC gather width (power-of-two bucket of the
        # pool's live max_blocks_in_use watermark): each distinct bucket
        # is one extra specialization, log2(max_blocks) worst case.
        bounded = self.bounded_gather
        self._step1 = jax.jit(under_ctx(
            lambda p, t, a, s, gw: lm.decode_step(
                p, t, s, cfg, active=a, gather_width=gw, bounded=bounded),
            "step_single"), static_argnums=(4,))
        self._stepC = jax.jit(under_ctx(
            lambda p, t, c, s, gw: lm.decode_chunk(
                p, t, c, s, cfg, gather_width=gw, bounded=bounded),
            "step_chunk"), static_argnums=(4,))
        # the K-step decode megatick: sampling runs INSIDE the scan
        # (greedy argmax, or the seeded batch sampler whose keys fold
        # (seed, rid, token index) with the scan step offsetting each
        # slot's token index), so only (B, K) token ids come back to
        # host. K is a static arg bucketed like gather_width.
        base_key = self._base_key
        in_scan = sampler != "greedy"

        def _megatick_fn(p, t, bud, s, rids, st0, tmp, tk, K, gw):
            if in_scan:
                def sample_fn(lg, j):
                    return sampler_lib.sample_batch(lg, base_key, rids,
                                                    st0 + j, tmp, tk)
            else:
                def sample_fn(lg, j):
                    return sampler_lib.greedy(lg)
            return lm.decode_multi(p, t, s, cfg, steps=K, budgets=bud,
                                   sample_fn=sample_fn, gather_width=gw,
                                   bounded=bounded)

        self._stepK = jax.jit(under_ctx(_megatick_fn, "megatick_decode"),
                              static_argnums=(8, 9))

        # the mixed prefill+decode megatick: one fused program in which
        # each slot consumes its next prompt-chunk tokens and/or runs
        # sample-fed decode steps (lm.decode_mixed). The sampler's
        # per-slot token index is st0 + (j - e0): the slot's emitted
        # count when the megatick started, offset by how many steps it
        # has been emitting — identical to the key fold every other
        # path uses, so streams stay scheduling-independent.
        def _mixedtick_fn(p, toks, tok0, pl, e0, tot, s, rids, st0, tmp,
                          tk, S, gw):
            if in_scan:
                def sample_fn(lg, j):
                    return sampler_lib.sample_batch(lg, base_key, rids,
                                                    st0 + j - e0, tmp, tk)
            else:
                def sample_fn(lg, j):
                    return sampler_lib.greedy(lg)
            return lm.decode_mixed(p, toks, tok0, pl, e0, tot, s, cfg,
                                   steps=S, sample_fn=sample_fn,
                                   gather_width=gw, bounded=bounded)

        self._stepM = jax.jit(under_ctx(_mixedtick_fn, "megatick_mixed"),
                              static_argnums=(11, 12))
        self._sample = jax.jit(sampler_lib.sample_batch)
        self._greedy = jax.jit(sampler_lib.greedy)

    # ------------------------------------------------------------- queueing
    def submit(self, req: Request, at_tick: int | None = None):
        """Queue a request. ``at_tick`` (or ``req.arrival_tick``) delays
        admission until that scheduler tick — this is how staggered
        arrivals are expressed in tests/benchmarks."""
        if not req.prompt:
            raise ValueError(
                f"request {req.rid}: empty prompt — a request must carry "
                f"at least one token to produce logits; reject it at the "
                f"API edge or seed it with a BOS token")
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} "
                f">= max_len {self.max_len} — the cache cannot hold the "
                f"prompt plus one generated token; raise max_len or "
                f"truncate the prompt")
        if not self.pool.admissible(len(req.prompt)):
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} "
                f"needs more KV blocks than the whole pool holds "
                f"(n_blocks={self.pool.n_blocks}, block_size="
                f"{self.pool.block_size}) — it could never be admitted; "
                f"raise n_blocks")
        req.submitted_t = time.time()
        if not req.arrived_t:           # the server stamps it at accept
            req.arrived_t = time.monotonic()
        req.seq = self._seq
        self._seq += 1
        if at_tick is not None:
            req.arrival_tick = at_tick
        self.queue.append(req)

    def _admit(self):
        """Admit eligible requests in policy order. Admission is gated
        on BLOCK availability, not just slot count: a request enters
        only when the pool can cover its (non-reused) effective prompt
        plus one generated token; the first request the pool cannot
        back stops admission for the tick — skipping ahead within the
        policy's order would starve long prompts."""
        admitted = []
        if not self.queue:
            return admitted
        eligible = [r for r in self.queue
                    if r.arrival_tick <= self.tick_count]
        if not eligible:
            return admitted
        taken = set()
        for req in self.policy.select_admissions(eligible, self.pool,
                                                 self.tick_count):
            if not self.pool.n_free:
                break
            res = self.pool.alloc(req.eff_prompt)
            if res is None:                 # not enough blocks yet
                break
            slot, reused = res
            req.slot = slot
            req.consumed = req.reused_tokens = reused
            if not req.admitted_t:
                # first admission: its wait counts once — re-admissions
                # after a preemption or a drain do not count again
                self.queue_wait_s += time.monotonic() - req.arrived_t
                self.admission_count += 1
            req.admitted_t = time.time()
            self.active[slot] = req
            taken.add(id(req))
            admitted.append(req)
        if taken:
            self.queue = deque(r for r in self.queue
                               if id(r) not in taken)
        return admitted

    def _preempt_one(self):
        """Every active slot is stalled on block availability and
        nothing can finish to free blocks: evict the policy's victim.
        Its effective prompt absorbs the tokens generated so far, its
        fully-written chunks re-register as prefix blocks (resume is a
        prefix hit), its private blocks return to the pool, and it goes
        back to the queue head. Raises only when the victim's history
        has outgrown the whole pool — no schedule can finish it."""
        victim = self.policy.select_victim(self.active, self.pool)
        victim.eff_prompt = list(victim.prompt) + list(victim.out_tokens)
        if not self.pool.admissible(len(victim.eff_prompt)):
            raise RuntimeError(
                f"KV block pool exhausted and request {victim.rid} has "
                f"grown past what the whole pool can hold (effective "
                f"prompt {len(victim.eff_prompt)} tokens, n_blocks="
                f"{self.pool.n_blocks}, block_size="
                f"{self.pool.block_size}): preemption cannot make "
                f"progress; raise n_blocks or lower max_new_tokens")
        slot = victim.slot
        self.pool.preempt(slot, victim.eff_prompt)
        del self.active[slot]
        victim.slot = -1
        victim.consumed = 0
        victim.reused_tokens = 0
        victim.preemptions += 1
        self.preempt_count += 1
        # queue head: the victim is in-flight work — every policy gets
        # first say on it again next tick via select_admissions
        self.queue.appendleft(victim)

    def cancel(self, rid: int) -> bool:
        """Abort request ``rid`` mid-stream: the user hung up, a server
        timeout fired, or an operator killed the stream. Returns True
        when the request was found (queued or active), False otherwise
        (already finished, already cancelled, or never submitted).

        A QUEUED request simply leaves the queue. An ACTIVE one takes
        the ``CachePool.abort`` path: its fully-written chunks register
        as prefix blocks (a later identical prompt is still a prefix
        hit), every block reference drops — private blocks return to
        the free list, registered ones stay LRU-resident as eviction
        supply — and the device-side slot resets, so the freed blocks
        are re-allocatable by the very next admission.

        Call BETWEEN ticks (the serving front-end's drive loop applies
        cancellations at megatick boundaries): an in-flight megatick
        always completes, and because every surviving stream's tokens
        depend only on its own history and its (seed, rid, token-index)
        sampler keys, cancelling a co-batched slot never perturbs the
        survivors — they stay token-identical to solo runs (the
        serve-smoke CI gate asserts this end to end)."""
        for req in self.queue:
            if req.rid == rid and not req.done:
                self.queue.remove(req)
                req.done = True
                req.cancelled = True
                req.finish_reason = req.finish_reason or "cancelled"
                self.cancel_count += 1
                return True
        for slot, req in list(self.active.items()):
            if req.rid != rid:
                continue
            # register what was actually written: the consumed prompt
            # prefix plus the generated history (same fold preemption
            # uses), so the abort leaves a warm prefix cache behind
            history = list(req.eff_prompt) + list(req.out_tokens)
            self.blocks_freed_on_abort += self.pool.abort(slot, history)
            del self.active[slot]
            req.slot = -1
            req.done = True
            req.cancelled = True
            req.finish_reason = req.finish_reason or "cancelled"
            self.cancel_count += 1
            return True
        return False

    def _retire(self, slot: int, req: Request, now: float, finished):
        """Retire a finished request: shared by the single-step and
        megatick paths so the decode_steps=1 vs K>1 identity the gates
        rely on cannot drift through one-sided edits."""
        req.done = True
        req.finish_reason = req.finish_reason or "length"
        req.finished_t = now
        finished.append(req)
        del self.active[slot]
        self.pool.free(slot)

    # ------------------------------------------------------- fault plane
    @property
    def eff_decode_steps(self) -> int:
        """Megatick length after the degraded-mode ladder: level 1
        halves K, level >= 2 forces the single-step path. Every level
        is token-identical to the configured K (the gated K-variation
        invariant) — degrading trades throughput for stability, never
        correctness."""
        if self.degraded is None or self.degraded.level == 0:
            return self.decode_steps
        if self.degraded.level == 1:
            return max(self.decode_steps // 2, 1)
        return 1

    @property
    def shedding(self) -> bool:
        """Level 3: the front-end should refuse new intake (429)."""
        return self.degraded is not None and self.degraded.level >= 3

    def _poll_fault(self, site: str):
        """The (tick, site)-keyed injection lookup; None when no plan
        is armed or the key already fired."""
        if self.faults is None:
            return None
        return self.faults.poll(site, self.tick_count)

    def _apply_faults(self):
        """Tick-boundary fault application: pool-exhaustion spikes
        (seize free blocks now, release them when the hold expires)
        and slow ticks (injected wall-clock stall, watchdog food).
        Dispatch and token faults are applied at their own sites."""
        if self._spike_until is not None \
                and self.tick_count >= self._spike_until:
            self.pool.release_seized()
            self._spike_until = None
        if self.faults is None:
            return
        spec = self.faults.poll("pool", self.tick_count)
        if spec is not None:
            self.pool.seize_blocks(spec.blocks)
            self._spike_until = self.tick_count + max(spec.hold_ticks, 1)
        spec = self.faults.poll("slow", self.tick_count)
        if spec is not None:
            time.sleep(spec.delay_s)

    def _backoff(self, attempt: int):
        """Deterministic exponential backoff between dispatch attempts
        (no jitter: one engine, one schedule — replayable; the CLIENT
        side decorrelates with full jitter instead)."""
        self.dispatch_retry_count += 1
        time.sleep(backoff_s(attempt, self.retry_backoff_s,
                             self.retry_backoff_cap_s))

    def _retire_error(self, slot: int, req: Request, now: float,
                      finished, reason: str):
        """Per-request error isolation: retire a POISONED slot through
        the ``CachePool.abort`` path with ``finish_reason="error"``.
        Only the pre-poison history (consumed prompt prefix + tokens
        that passed the guard) is registered into the prefix cache —
        ``register_prompt_chunks`` registers ``min(written, len(tokens))``
        worth of full chunks, so KV written past the clean history can
        never be served to a future prefix hit. Co-batched survivors
        are untouched: their streams depend only on their own history
        and (seed, rid, token-index) sampler keys, so they stay
        token-identical to a fault-free run (gated by BENCH_ci gate 5
        and tests/test_faults.py)."""
        history = (list(req.eff_prompt[:req.consumed])
                   + list(req.out_tokens))
        self.pool.abort(slot, history)
        del self.active[slot]
        req.slot = -1
        req.done = True
        req.error = reason
        req.finish_reason = "error"
        req.finished_t = now
        self.error_count += 1
        finished.append(req)

    # ----------------------------------------------------------- scheduling
    def tick(self) -> list[Request]:
        """One scheduler step. Returns requests that finished this tick.

        Wraps the dispatch path with the robustness plane: the
        megatick wall-clock watchdog (monotonic clock — serving
        megaticks are milliseconds, NTP slew would look like a
        straggler) and the degraded-mode ladder, which observes
        adverse ticks (watchdog-slow, dispatch retries, poisoned
        slots) and steps K/gather-mode/shedding down under sustained
        pressure, back up after sustained health."""
        r0, e0 = self.dispatch_retry_count, self.error_count
        with self.spans.span("engine.tick", tick=self.tick_count + 1) as sp:
            finished = self._tick()
        slow = self.watchdog.record(self.tick_count, sp.seconds)
        if slow:
            self.slow_tick_count += 1
        if self.degraded is not None:
            adverse = (slow or self.dispatch_retry_count > r0
                       or self.error_count > e0)
            lvl = self.degraded.observe(adverse)
            want_bounded = self._cfg_bounded and lvl < 2
            if want_bounded != self.bounded_gather:
                self.bounded_gather = want_bounded
                self._build_dispatchers()
        self.policy.on_tick_end(self.queue, self.active, self.tick_count)
        return finished

    def _tick(self) -> list[Request]:
        with self.spans.span("engine.admit", tick=self.tick_count + 1) as sp:
            admitted = self._admit()
            if admitted:
                sp.set_metadata(rids=" ".join(str(r.rid) for r in admitted))
        self.tick_count += 1
        self._apply_faults()
        if not self.active:
            return []
        if self.eff_decode_steps > 1:
            # megatick engines never fall back to one-dispatch-per-token:
            # a batch with prefill in flight runs the fused MIXED program
            # (prompt chunks piggyback on the decode scan), a pure-decode
            # batch keeps the K-step fast path
            if any(r.prefilling for r in self.active.values()):
                return self._megatick_mixed()
            return self._megatick()
        C = self.prefill_chunk
        with self.spans.span("engine.prepare", tick=self.tick_count):
            tok = np.zeros((self.batch, C), np.int32)
            cnt = np.zeros((self.batch,), np.int32)
            emit = np.zeros((self.batch,), bool)
            any_prefill = False
            for slot, req in self.active.items():
                want = (min(C, len(req.eff_prompt) - req.consumed)
                        if req.prefilling else 1)
                # clamp to what the pool can back with blocks this tick
                # (allocates at chunk boundaries, copy-on-writes shared
                # blocks)
                n = self.pool.writable(slot, want)
                if n == 0:
                    continue                # stalled: no KV block free
                if req.prefilling:
                    any_prefill = True
                    tok[slot, :n] = \
                        req.eff_prompt[req.consumed:req.consumed + n]
                    cnt[slot] = n
                    emit[slot] = req.consumed + n >= len(req.eff_prompt)
                else:
                    tok[slot, 0] = (req.out_tokens[-1] if req.out_tokens
                                    else req.eff_prompt[-1])
                    cnt[slot] = 1
                    emit[slot] = True

        cmax = int(cnt.max(initial=0))
        if cmax == 0:
            # every active slot stalled and nothing can finish to free
            # blocks — preempt a victim instead of deadlocking; its
            # blocks unblock the survivors next tick
            self._preempt_one()
            return []
        with self.spans.span("engine.upload", tick=self.tick_count):
            self.pool.sync()
        # gather width AFTER the writable() loop: this tick's block
        # allocations are in the table, so the bucket covers every
        # position the jitted step will read or write
        gw = self.pool.gather_width()
        # bucket the scan length to the next power of two so ticks with
        # little prefill left don't pay the full chunk, while compile
        # count stays bounded at log2(prefill_chunk)
        cw = 1 if cmax <= 1 else pow2_bucket(cmax, C)
        self._count_dispatch(gw)
        if not any_prefill:
            self.decode_dispatch_count += 1
        self.scan_slot_steps += int(cnt.sum())
        self.scan_slot_capacity += cw * self.batch
        # bounded retry-with-backoff around the ONE jitted dispatch:
        # pool state commits only on success, so a retried attempt
        # replays identical inputs (transient failures are safe to
        # retry; retries count against the TAX003 budget as real
        # worst-case dispatches — DISPATCH_ATTEMPTS is a static trip)
        with self.spans.span("engine.dispatch", tick=self.tick_count):
            fault = self._poll_fault("dispatch")
            for attempt in range(DISPATCH_ATTEMPTS):
                if attempt:
                    self._backoff(attempt)
                try:
                    if fault is not None:
                        fault.trip()
                    if cmax <= 1:
                        logits, state = self._step1(
                            self.params, jnp.asarray(tok[:, :1]),
                            jnp.asarray(cnt > 0), self.pool.state, gw)
                    else:
                        logits, state = self._stepC(
                            self.params, jnp.asarray(tok[:, :cw]),
                            jnp.asarray(cnt), self.pool.state, gw)
                    break
                except TransientDispatchError as err:
                    last_err = err
            else:
                self.dispatch_failure_count += 1
                raise DispatchFailedError(
                    f"dispatch failed after {DISPATCH_ATTEMPTS} attempts "
                    f"at tick {self.tick_count}") from last_err
            self.pool.state = state
        with self.spans.span("engine.readback", tick=self.tick_count):
            nxt = self._next_tokens(logits, emit)

        with self.spans.span("engine.retire", tick=self.tick_count):
            poison = self._poll_fault("tokens")
            if poison is not None:
                # the host-visible signature of NaN/Inf logits: a
                # garbage (out-of-range) sampled id for exactly one slot
                nxt[poison.slot % self.batch, :] = -1

            finished = []
            now = time.time()
            for slot, req in list(self.active.items()):
                n = int(cnt[slot])
                if n == 0:
                    continue
                self.pool.advance(slot, n)
                cache_full = int(self.pool.lengths[slot]) + 1 >= self.max_len
                if req.prefilling:
                    req.consumed += n
                    # full prompt chunks just written become shareable
                    # prefix blocks for future admissions (and for
                    # resuming this request if it is ever preempted)
                    self.pool.register_prompt_chunks(slot, req.eff_prompt)
                if self.cfg.sliding_window is not None:
                    # block-level reclaim: positions that rolled
                    # permanently out of the window stop pinning their
                    # blocks
                    self.pool.reclaim_out_of_window(slot,
                                                    self.cfg.sliding_window)
                if req.prefilling and not cache_full:   # still mid-prompt
                    continue
                if not req.prefilling:
                    # the logits after this slot's last consumed token
                    # give the next output token (the first one arrives
                    # on the tick that completes the prefill)
                    t = int(nxt[slot, 0])
                    if not 0 <= t < self.cfg.vocab_size:
                        # NaN/Inf guard: a sampled id outside the vocab
                        # is the readback signature of non-finite logits
                        # — retire THIS slot as an error, survivors
                        # untouched
                        self._retire_error(
                            slot, req, now, finished,
                            f"non-finite logits: sampled token id {t}")
                        continue
                    req.out_tokens.append(t)
                    if not any_prefill:
                        self.decode_token_count += 1
                    if len(req.out_tokens) == 1:
                        req.first_token_t = now
                if (len(req.out_tokens) >= req.max_new_tokens
                        or cache_full):
                    self._retire(slot, req, now, finished)
        return finished

    def _megatick(self) -> list[Request]:
        """One fused K-step decode dispatch (``lm.decode_multi``): runs
        only when every active slot is decoding. Each slot's step budget
        is clamped by its remaining ``max_new_tokens``, its ``max_len``
        headroom, and the blocks ``CachePool.reserve`` can pre-allocate
        for the whole megatick; a slot past its budget freezes
        byte-identically inside the scan. Sampling is device-resident —
        the host gets back (B, K) token ids, not K logit tensors."""
        K = self.eff_decode_steps
        with self.spans.span("engine.prepare", tick=self.tick_count):
            tok = np.zeros((self.batch, 1), np.int32)
            budgets = np.zeros((self.batch,), np.int32)
            rids = np.zeros((self.batch,), np.int32)
            steps0 = np.zeros((self.batch,), np.int32)
            temps = np.zeros((self.batch,), np.float32)
            topks = np.zeros((self.batch,), np.int32)
            for slot, req in self.active.items():
                # a live decode slot always wants >= 1 step (it would have
                # been retired last tick otherwise); the reservation may
                # still return 0 under pool pressure -> the slot stalls
                want = min(K, req.max_new_tokens - len(req.out_tokens),
                           self.max_len - 1 - int(self.pool.lengths[slot]))
                budgets[slot] = self.pool.reserve(slot, want)
                tok[slot, 0] = (req.out_tokens[-1] if req.out_tokens
                                else req.eff_prompt[-1])
                rids[slot] = req.rid
                steps0[slot] = len(req.out_tokens)
                temps[slot] = req.temp
                topks[slot] = req.top_k
        kmax = int(budgets.max(initial=0))
        if kmax == 0:
            # every slot stalled on block availability at the megatick
            # boundary: preempt the policy's victim, as the single-step
            # path does
            self._preempt_one()
            return []
        with self.spans.span("engine.upload", tick=self.tick_count):
            self.pool.sync()
        # gather width AFTER the reserve() loop: the static slice must
        # cover every block the whole megatick writes
        gw = self.pool.gather_width()
        # bucket the scan length to the next power of two (clamped at
        # K) so ragged tail megaticks don't pay the full K while jit
        # specializations stay bounded at log2(decode_steps)
        kb = pow2_bucket(kmax, K)
        self._count_dispatch(gw)
        self.decode_dispatch_count += 1
        self.scan_slot_steps += int(budgets.sum())
        self.scan_slot_capacity += kb * self.batch
        # bounded retry-with-backoff: pool state commits only on
        # success, so a retried attempt replays identical inputs
        with self.spans.span("engine.dispatch", tick=self.tick_count):
            fault = self._poll_fault("dispatch")
            for attempt in range(DISPATCH_ATTEMPTS):
                if attempt:
                    self._backoff(attempt)
                try:
                    if fault is not None:
                        fault.trip()
                    out, state = self._stepK(
                        self.params, jnp.asarray(tok), jnp.asarray(budgets),
                        self.pool.state, jnp.asarray(rids),
                        jnp.asarray(steps0), jnp.asarray(temps),
                        jnp.asarray(topks), kb, gw)
                    break
                except TransientDispatchError as err:
                    last_err = err
            else:
                self.dispatch_failure_count += 1
                raise DispatchFailedError(
                    f"megatick dispatch failed after {DISPATCH_ATTEMPTS} "
                    f"attempts at tick {self.tick_count}") from last_err
            self.pool.state = state
        with self.spans.span("engine.readback", tick=self.tick_count):
            # taxlint: ignore[TAX001] the megatick's ONE designed sync:
            # (B, K) token ids — not K logit tensors — come back to drive
            # Python-side scheduling; amortized over K tokens, this IS the
            # 1/K bound
            out = np.asarray(out)
        with self.spans.span("engine.retire", tick=self.tick_count):
            poison = self._poll_fault("tokens")
            if poison is not None:
                # the host-visible signature of NaN/Inf logits mid-megatick
                out = out.copy()
                out[poison.slot % self.batch, :] = -1

            finished = []
            now = time.time()
            for slot, req in list(self.active.items()):
                n = int(budgets[slot])
                if n == 0:
                    continue
                row = out[slot, :n]
                bad = np.nonzero((row < 0) | (row >= self.cfg.vocab_size))[0]
                if bad.size:
                    # NaN/Inf guard: keep the tokens sampled BEFORE the
                    # first garbage id (their logits were still finite),
                    # advance the host length mirror only that far so the
                    # prefix registry can never serve poisoned KV, and
                    # retire THIS slot as an error — survivors untouched
                    good = int(bad[0])
                    self.pool.advance(slot, good)
                    req.out_tokens.extend(int(t) for t in row[:good])
                    self.decode_token_count += good
                    self._retire_error(
                        slot, req, now, finished,
                        f"non-finite logits: sampled token id "
                        f"{int(row[good])}")
                    continue
                self.pool.advance(slot, n)
                req.out_tokens.extend(int(t) for t in row)
                self.decode_token_count += n
                if self.cfg.sliding_window is not None:
                    self.pool.reclaim_out_of_window(slot,
                                                    self.cfg.sliding_window)
                cache_full = int(self.pool.lengths[slot]) + 1 >= self.max_len
                if (len(req.out_tokens) >= req.max_new_tokens
                        or cache_full):
                    self._retire(slot, req, now, finished)
        return finished

    def _megatick_mixed(self) -> list[Request]:
        """One fused mixed prefill+decode dispatch (``lm.decode_mixed``):
        runs whenever a K-step engine has ANY slot mid-prompt — the
        production steady state under continuous arrivals, where the
        pure-decode megatick cannot engage. Each slot gets a per-megatick
        token quota of ``megatick_tokens`` (M) split between roles:

        * a PREFILLING slot consumes ``p = min(M, remaining prompt)``
          prompt tokens; if that completes its prompt, it samples its
          first token at the step that consumed the last prompt token
          (not next tick) and piggybacks up to
          ``min(M - p, K, remaining max_new - 1, headroom)`` further
          decode steps in the same dispatch;
        * a DECODING slot runs its usual ``min(K, remaining max_new,
          headroom)`` step budget.

        One ``CachePool.reserve`` call per slot pre-allocates blocks for
        ALL of the megatick's writes — prompt chunks and decode steps
        alike — and a short reservation shrinks the prefill span first
        (clamping decode piggybacking to zero), so the scan never writes
        an unbacked position. Sampling is device-resident; the host gets
        back (B, S) token ids, S pow2-bucketed and capped at M. If every
        slot's reservation is 0, the policy's victim is preempted, as
        every other dispatch path does."""
        K = self.eff_decode_steps
        M = self.megatick_tokens
        with self.spans.span("engine.prepare", tick=self.tick_count):
            toks = np.zeros((self.batch, M), np.int32)
            tok0 = np.zeros((self.batch, 1), np.int32)
            pl = np.zeros((self.batch,), np.int32)     # prefill role steps
            e0 = np.zeros((self.batch,), np.int32)     # first emitting step
            tot = np.zeros((self.batch,), np.int32)    # total active steps
            rids = np.zeros((self.batch,), np.int32)
            steps0 = np.zeros((self.batch,), np.int32)
            temps = np.zeros((self.batch,), np.float32)
            topks = np.zeros((self.batch,), np.int32)
            for slot, req in self.active.items():
                headroom = self.max_len - 1 - int(self.pool.lengths[slot])
                rem_new = req.max_new_tokens - len(req.out_tokens)
                if req.prefilling:
                    rem_p = len(req.eff_prompt) - req.consumed
                    p_want = min(M, rem_p)
                    # decode piggybacking only when the prompt completes
                    # inside this megatick; the first sampled token is free
                    # (its KV write happens when it is consumed), so the
                    # decode span is capped at remaining max_new MINUS one
                    d_want = (max(0, min(M - p_want, K, rem_new - 1,
                                         headroom - p_want))
                              if p_want == rem_p else 0)
                else:
                    rem_p = 0
                    p_want = 0
                    d_want = min(K, rem_new, headroom)
                n = self.pool.reserve(slot, p_want + d_want)
                p = min(n, p_want)
                tot[slot] = n
                pl[slot] = p
                # emission starts at the step consuming the LAST prompt
                # token (first sampled token rides its logits) — or at step
                # 0 for slots already decoding; a slot whose prompt does
                # not complete this megatick never emits (e0 == n)
                e0[slot] = max(p - 1, 0) if p == rem_p else n
                toks[slot, :p] = req.eff_prompt[req.consumed:req.consumed + p]
                tok0[slot, 0] = (req.out_tokens[-1] if req.out_tokens
                                 else req.eff_prompt[-1])
                rids[slot] = req.rid
                steps0[slot] = len(req.out_tokens)
                temps[slot] = req.temp
                topks[slot] = req.top_k
        nmax = int(tot.max(initial=0))
        if nmax == 0:
            # every slot stalled on block availability at the megatick
            # boundary: preempt the policy's victim, as the other
            # dispatch paths do
            self._preempt_one()
            return []
        with self.spans.span("engine.upload", tick=self.tick_count):
            self.pool.sync()
        # gather width AFTER the reserve() loop: the static slice must
        # cover every block the whole megatick writes, prompt chunks
        # included
        gw = self.pool.gather_width()
        # scan length bucketed to the next power of two, capped at the
        # megatick token quota: jit specializations stay bounded at
        # log2(M) while ragged ticks don't pay the full quota
        S = pow2_bucket(nmax, M)
        self._count_dispatch(gw)
        self.mixed_dispatch_count += 1
        self.mixed_prompt_token_count += int(pl.sum())
        self.scan_slot_steps += int(tot.sum())
        self.scan_slot_capacity += S * self.batch
        # bounded retry-with-backoff: pool state commits only on
        # success, so a retried attempt replays identical inputs
        with self.spans.span("engine.dispatch", tick=self.tick_count):
            fault = self._poll_fault("dispatch")
            for attempt in range(DISPATCH_ATTEMPTS):
                if attempt:
                    self._backoff(attempt)
                try:
                    if fault is not None:
                        fault.trip()
                    out, state = self._stepM(
                        self.params, jnp.asarray(toks[:, :S]),
                        jnp.asarray(tok0), jnp.asarray(pl), jnp.asarray(e0),
                        jnp.asarray(tot), self.pool.state, jnp.asarray(rids),
                        jnp.asarray(steps0), jnp.asarray(temps),
                        jnp.asarray(topks), S, gw)
                    break
                except TransientDispatchError as err:
                    last_err = err
            else:
                self.dispatch_failure_count += 1
                raise DispatchFailedError(
                    f"mixed megatick dispatch failed after "
                    f"{DISPATCH_ATTEMPTS} attempts at tick "
                    f"{self.tick_count}") from last_err
            self.pool.state = state
        with self.spans.span("engine.readback", tick=self.tick_count):
            # taxlint: ignore[TAX001] the mixed megatick's ONE designed sync:
            # (B, S) sampled-token ids — not per-step logit tensors — come
            # back to drive Python-side scheduling; amortized over the
            # megatick's prompt+decode tokens, this IS the 1/K bound under
            # continuous arrivals
            out = np.asarray(out)
        with self.spans.span("engine.retire", tick=self.tick_count):
            poison = self._poll_fault("tokens")
            if poison is not None:
                # the host-visible signature of NaN/Inf logits mid-megatick
                out = out.copy()
                out[poison.slot % self.batch, :] = -1

            finished = []
            now = time.time()
            for slot, req in list(self.active.items()):
                n = int(tot[slot])
                if n == 0:
                    continue
                p = int(pl[slot])
                first_emit = int(e0[slot])
                emitted = n - first_emit
                span = out[slot, first_emit:n] if emitted > 0 \
                    else out[slot, :0]
                bad = np.nonzero((span < 0)
                                 | (span >= self.cfg.vocab_size))[0]
                if bad.size:
                    # NaN/Inf guard, mixed shape: prompt-chunk writes are
                    # real tokens (always clean); of the sampled span keep
                    # only the ids before the first garbage one. Advance
                    # the host length mirror over prompt writes + clean
                    # sampled writes so the prefix registry never serves
                    # poisoned KV, then retire THIS slot as an error.
                    good = int(bad[0])
                    self.pool.advance(slot, min(n, p + good))
                    req.consumed += p
                    req.out_tokens.extend(int(t) for t in span[:good])
                    self.mixed_decode_token_count += good
                    self._retire_error(
                        slot, req, now, finished,
                        f"non-finite logits: sampled token id "
                        f"{int(span[good])}")
                    continue
                self.pool.advance(slot, n)
                if p:
                    req.consumed += p
                    # full prompt chunks just written become shareable
                    # prefix blocks, exactly as on a single-step tick
                    self.pool.register_prompt_chunks(slot, req.eff_prompt)
                if self.cfg.sliding_window is not None:
                    self.pool.reclaim_out_of_window(slot,
                                                    self.cfg.sliding_window)
                if emitted > 0:
                    first = not req.out_tokens
                    req.out_tokens.extend(int(t) for t in span)
                    self.mixed_decode_token_count += emitted
                    if first:
                        req.first_token_t = now
                cache_full = int(self.pool.lengths[slot]) + 1 >= self.max_len
                if req.prefilling and not cache_full:
                    continue
                if (len(req.out_tokens) >= req.max_new_tokens
                        or cache_full):
                    self._retire(slot, req, now, finished)
        return finished

    def _next_tokens(self, logits, emit):
        """Sample each emitting slot's next token. Greedy engines keep
        the PR-1 argmax path byte-identical; temperature engines fold
        (seed, rid, token index) into a per-slot key so outputs are
        reproducible and independent of batch composition."""
        if self.sampler == "greedy":
            # jitted like self._sample: the un-jitted call paid a
            # trace-free op-by-op dispatch every single-step tick
            # taxlint: ignore[TAX001] single-step ticks need the sampled
            # (B, 1) ids on host to retire/requeue; megaticks amortize this
            # to once per K steps
            return np.asarray(self._greedy(logits))
        rids = np.zeros((self.batch,), np.int32)
        steps = np.zeros((self.batch,), np.int32)
        temps = np.zeros((self.batch,), np.float32)
        topks = np.zeros((self.batch,), np.int32)
        for slot, req in self.active.items():
            if not emit[slot]:
                continue
            rids[slot] = req.rid
            steps[slot] = len(req.out_tokens)
            temps[slot] = req.temp
            topks[slot] = req.top_k
        # taxlint: ignore[TAX001] same designed once-per-dispatch readback
        # as the greedy path: (B, 1) sampled ids, not the (B, V) logits
        return np.asarray(self._sample(logits, self._base_key,
                                       jnp.asarray(rids),
                                       jnp.asarray(steps),
                                       jnp.asarray(temps),
                                       jnp.asarray(topks)))

    def run(self, max_ticks: int = 10_000) -> list[Request]:
        """Run until all submitted requests finish (or max_ticks ticks
        elapse IN THIS CALL — the budget is per-call, so a long-lived
        engine can drain, accept new submits, and run again)."""
        finished = []
        start = self.tick_count
        while ((self.queue or self.active)
               and self.tick_count - start < max_ticks):
            finished.extend(self.tick())
        return finished

    # --------------------------------------------- drain / snapshot / restore
    def drain(self) -> list[Request]:
        """Park every in-flight request at a clean boundary: each
        ACTIVE slot takes the preemption path (generated tokens fold
        into the effective prompt, fully-written chunks register as
        prefix blocks, private blocks free), then rejoins the queue
        AHEAD of never-started requests in slot order. After drain the
        engine holds no active slots and any seized fault-injection
        blocks are back in the pool — the state is checkpointable, and
        resuming (here or in a restored engine) re-admits every parked
        request as a prefix hit. Returns the drained queue snapshot."""
        if self._spike_until is not None:
            self.pool.release_seized()
            self._spike_until = None
        parked = []
        for slot in sorted(self.active):
            req = self.active[slot]
            req.eff_prompt = list(req.prompt) + list(req.out_tokens)
            self.pool.preempt(slot, req.eff_prompt)
            req.slot = -1
            req.consumed = 0
            req.reused_tokens = 0
            parked.append(req)
        self.active.clear()
        for req in reversed(parked):
            self.queue.appendleft(req)
        self.drain_count += len(parked)
        return list(self.queue)

    def _req_payload(self, req: Request) -> dict:
        return {"rid": req.rid, "prompt": list(req.prompt),
                "max_new_tokens": req.max_new_tokens,
                "temp": req.temp, "top_k": req.top_k,
                "priority": req.priority, "deadline_ms": req.deadline_ms,
                "out_tokens": list(req.out_tokens),
                "preemptions": req.preemptions, "seq": req.seq,
                "submitted_t": req.submitted_t,
                "first_token_t": req.first_token_t}

    def snapshot(self, ckpt, step: int | None = None,
                 block: bool = True) -> int:
        """Drain, then persist the full serving state through a
        ``checkpoint.Checkpointer``: the device-side pool state pytree
        (KV pages, tables, positions) as the checkpoint tree, and the
        JSON-able host half — queued requests (with generated-so-far
        tokens) plus the pool's host bookkeeping incl. the prefix-chain
        registry — in the manifest's ``extra``. A killed server that
        restores this resumes every unfinished request as a PREFIX HIT:
        the KV it already computed is still resident. Returns the step
        the checkpoint was written under."""
        self.drain()
        step = self.tick_count if step is None else step
        extra = {"serving": {
            "sampler": self.sampler, "seed": self.seed,
            "requests": [self._req_payload(r) for r in self.queue],
            "pool": self.pool.snapshot_meta(),
        }}
        # npz can't round-trip ml_dtypes (bf16 KV pages come back as
        # raw void): widen those leaves to float32 — exact for bf16 —
        # and restore() narrows them back to the live state's dtypes
        def _cast(x):
            x = np.asarray(x)
            return (np.asarray(x, np.float32)
                    if x.dtype.kind not in "fiub" else x)
        tree = jax.tree_util.tree_map(_cast, self.pool.state)
        ckpt.save(step, tree, extra=extra, block=block)
        return step

    def restore(self, ckpt, step: int | None = None) -> list[Request]:
        """Load a :meth:`snapshot` into THIS engine (freshly built with
        the same pool geometry, sampler, and seed — geometry is
        validated, identity knobs are asserted here because a
        different (sampler, seed) would silently change every resumed
        stream). Queued requests are rebuilt with their effective
        prompts (original prompt + generated tokens), so the next
        ticks re-admit them against the restored prefix registry: the
        blocks they already wrote are hits, not re-prefills. Returns
        the restored requests in queue order."""
        tree, manifest = ckpt.restore(step, self.pool.state)
        meta = manifest["extra"]["serving"]
        if (meta["sampler"], meta["seed"]) != (self.sampler, self.seed):
            raise ValueError(
                f"snapshot sampler/seed ({meta['sampler']!r}, "
                f"{meta['seed']}) != engine ({self.sampler!r}, "
                f"{self.seed}): restored streams would diverge")
        self.pool.state = jax.tree_util.tree_map(
            lambda cur, x: jnp.asarray(x, dtype=cur.dtype),
            self.pool.state, tree)
        self.pool.restore_meta(meta["pool"])
        self.queue.clear()
        restored = []
        for d in meta["requests"]:
            r = Request(rid=d["rid"], prompt=list(d["prompt"]),
                        max_new_tokens=d["max_new_tokens"],
                        temp=d["temp"], top_k=d["top_k"],
                        priority=d["priority"],
                        deadline_ms=d["deadline_ms"])
            r.out_tokens = list(d["out_tokens"])
            r.eff_prompt = list(r.prompt) + list(r.out_tokens)
            r.preemptions = d["preemptions"]
            r.seq = d["seq"]
            r.submitted_t = d["submitted_t"]
            r.first_token_t = d["first_token_t"]
            r.arrived_t = time.monotonic()
            r.arrival_tick = 0          # admissible immediately
            self.queue.append(r)
            restored.append(r)
        self._seq = max([r.seq for r in restored], default=-1) + 1
        return restored

    # -------------------------------------------------------------- metrics
    def metrics(self, done: list[Request]) -> dict:
        """``counters()`` and the latency summaries over ``done``."""
        # zero-output requests never produced a first token: excluding
        # them keeps the TTFT percentiles honest (request_latency)
        return {"requests": len(done),
                "new_tokens": sum(len(r.out_tokens) for r in done),
                **self.counters(),
                **latency_metrics([request_latency(r) for r in done])}

    def counters(self) -> dict:
        """The engine's counters and gauges: their cost does not grow
        with the requests served, so a server can snapshot them every
        tick."""
        return {
            "ticks": self.tick_count,
            "dispatches": self.dispatch_count,
            "decode_steps": self.decode_steps,
            "decode_dispatches": self.decode_dispatch_count,
            "decode_tokens": self.decode_token_count,
            # the megatick win, structurally: tokens produced per pure-
            # decode dispatch (>= decode_steps at steady state; the CI
            # gate asserts dispatches-per-token <= 1/K from these)
            "tokens_per_dispatch": round(
                self.decode_token_count
                / max(self.decode_dispatch_count, 1), 2),
            # mixed-megatick counters: fused dispatches carrying prompt
            # chunks, the prompt tokens they consumed, and the decode
            # tokens they emitted — what makes the dispatch amortization
            # visible under continuous arrivals
            "mixed_dispatches": self.mixed_dispatch_count,
            "mixed_prompt_tokens": self.mixed_prompt_token_count,
            "mixed_decode_tokens": self.mixed_decode_token_count,
            # single-device dispatches whose attention read the whole KV
            # pool once instead of gathering a per-slot view
            "attn_pool_view_dispatches": self.pool_view_dispatch_count,
            # the open-loop gate quantity: ALL fused decode-capable
            # dispatches (pure megaticks + mixed megaticks) per decode
            # token emitted — <= 1/K at steady state even with prefill
            # permanently in flight (the mixed BENCH_ci gate)
            "decode_dispatches_per_token": round(
                (self.decode_dispatch_count + self.mixed_dispatch_count)
                / max(self.decode_token_count
                      + self.mixed_decode_token_count, 1), 4),
            "scheduler": self.policy.name,
            "preemptions": self.preempt_count,
            # cancellation/abort counters: requests aborted mid-stream
            # (Engine.cancel — user hang-ups, server timeouts) and the
            # KV blocks those aborts made re-allocatable for subsequent
            # admissions (the serve-smoke CI gate quantity)
            "cancellations": self.cancel_count,
            "blocks_freed_on_abort": self.blocks_freed_on_abort,
            # robustness counters (docs/robustness.md): injected
            # faults, absorbed dispatch retries (they count against
            # the 1/K budget as real dispatches — gate 5's numerator
            # includes them), exhausted retry budgets, poisoned slots
            # retired finish_reason="error", watchdog-slow megaticks,
            # the degraded-mode ladder position, and drained requests
            "faults_injected": (self.faults.injected
                                if self.faults is not None else 0),
            "dispatch_retries": self.dispatch_retry_count,
            "dispatch_failures": self.dispatch_failure_count,
            "errors": self.error_count,
            "slow_ticks": self.slow_tick_count,
            "degraded_mode": (self.degraded.level
                              if self.degraded is not None else 0),
            "degraded_transitions": (self.degraded.transitions
                                     if self.degraded is not None else 0),
            "drained_requests": self.drain_count,
            # tracing: queue wait from arrival to first admission, the
            # scans' useful slot-steps against scan length x batch, and
            # the seconds of each tick span (engine_<phase>_s)
            "queue_wait_s": self.queue_wait_s,
            "admissions": self.admission_count,
            "scan_slot_steps": self.scan_slot_steps,
            "scan_slot_capacity": self.scan_slot_capacity,
            **self.spans.seconds,
            **self.pool.metrics(),
        }
