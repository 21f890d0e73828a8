"""Bounded table-gather paged decode: per-PR (fast tier) coverage.

The nightly battery proves bounded == masked across the bsp/ring modes
end to end; this file is the fast-tier net under it:

* raw-op tests drive ``decode_paged_attention_fused_sm`` on a 1-device
  mesh (the shard_map body runs with W == 1, so the bounded gather,
  hole masking, and gather-width slicing execute without fake devices);
* engine tests exercise the gather-width bucketing machinery end to
  end (the watermark, the static-width jit threading, and token
  identity through preemption-resume and sliding-window reclaim);
* one tiny 8-fake-device subprocess promotes the bsp-mode
  bounded-vs-masked check (``check_paged_bounded_gather_bsp_small``)
  into the per-PR tier.
"""
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core import flash_decode as fd
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving.engine import Engine, Request
from repro.serving.kv_cache import CachePool, pow2_bucket
from repro.testing.decode_reference import reference_generate
from repro.testing.distributed_checks import _paged_hole_oracle


def _setup(n_layers=2):
    cfg = smoke_config(get_config("llama3-8b")).replace(n_layers=n_layers)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _run_fused_1dev(q, k_new, v_new, k_pool, v_pool, cur, tables, *,
                    window=None, bounded=True):
    mesh = make_mesh((1,), ("model",))
    return jax.jit(
        lambda q, kn, vn, kp, vp, c, t:
        fd.decode_paged_attention_fused_sm(
            q, kn, vn, kp, vp, c, t, mesh, scale=0.25, mode="ring",
            window=window, bounded=bounded))(
        q, k_new, v_new, k_pool, v_pool, cur, tables)


def test_bounded_gather_masks_reclaim_holes():
    """A -1 hole mid-table (sliding-window reclaim) must never be
    scored: bounded output matches the hole-masking dense oracle, with
    and without a window, and the through-table write is exact."""
    B, H, KVH, D = 2, 4, 2, 8
    bs, n_blocks = 4, 8
    q = _rand(0, (B, H, D))
    k_pool = _rand(1, (n_blocks, bs, KVH, D))
    v_pool = _rand(2, (n_blocks, bs, KVH, D))
    k_new, v_new = _rand(3, (B, KVH, D)), _rand(4, (B, KVH, D))
    tables = jnp.array([[5, -1, 2, 7], [1, 3, -1, -1]], jnp.int32)
    cur = jnp.array([15, 8], jnp.int32)
    kp_ref, vp_ref = k_pool, v_pool
    for b in range(B):
        p = int(cur[b]) - 1
        blk = int(tables[b, p // bs])
        kp_ref = kp_ref.at[blk, p % bs].set(k_new[b])
        vp_ref = vp_ref.at[blk, p % bs].set(v_new[b])
    for window in (None, 6):
        want = _paged_hole_oracle(q, kp_ref, vp_ref, cur, tables, bs,
                                  0.25, window=window)
        out, ck, cv = _run_fused_1dev(q, k_new, v_new, k_pool, v_pool,
                                      cur, tables, window=window)
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(kp_ref))
        np.testing.assert_array_equal(np.asarray(cv), np.asarray(vp_ref))


def test_slot_at_exact_gather_width():
    """A slot whose length exactly fills the gather width (cur_len ==
    width * block_size) must attend its final position: no off-by-one
    at the bucket boundary, and a tighter slice that still covers all
    allocated entries changes nothing."""
    B, H, KVH, D = 1, 4, 2, 8
    bs, n_blocks = 4, 8
    q = _rand(0, (B, H, D))
    k_pool = _rand(1, (n_blocks, bs, KVH, D))
    v_pool = _rand(2, (n_blocks, bs, KVH, D))
    k_new, v_new = _rand(3, (B, KVH, D)), _rand(4, (B, KVH, D))
    full = jnp.array([[6, 1, 4, 2, -1, -1]], jnp.int32)
    cur = jnp.array([16], jnp.int32)        # fills blocks 0..3 exactly
    kp_ref = k_pool.at[2, 3].set(k_new[0])  # pos 15 -> table[3]=2, off 3
    vp_ref = v_pool.at[2, 3].set(v_new[0])
    want = _paged_hole_oracle(q, kp_ref, vp_ref, cur, full, bs, 0.25)
    outs = {}
    for width in (6, 4):                    # full table vs exact bucket
        out, ck, _ = _run_fused_1dev(q, k_new, v_new, k_pool, v_pool,
                                     cur, full[:, :width])
        np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(kp_ref))
        outs[width] = np.asarray(out)
    np.testing.assert_allclose(outs[4], outs[6], rtol=1e-6, atol=1e-6)


# Served single-device attention against the gathered float32 oracle,
# on both sides of the view rule: 4 slots, table width 4, blocks of 4,
# so a pool of 8 blocks is attended whole and one of 40 is gathered.
# Slot 0 shares its first block with slot 1 (a prefix hit); slot 2 is
# inactive (no write, its length unchanged); slot 3 is empty (length 0).
# Windowed cases hole slot 0's first column out of the window (-1, as
# sliding-window reclaim leaves it).
_SERVED_CASES = {
    # id: (kv heads of 4 query heads, pool blocks, window, pool dtype)
    "mha-whole-pool": (4, 8, None, jnp.bfloat16),
    "mha-gathered": (4, 40, None, jnp.bfloat16),
    "gqa-whole-pool": (2, 8, None, jnp.bfloat16),
    "gqa-gathered": (1, 40, None, jnp.bfloat16),
    "window-holes-whole-pool": (2, 8, 6, jnp.bfloat16),
    "window-holes-gathered": (2, 40, 6, jnp.bfloat16),
    "f32-pool-whole-pool": (2, 8, None, jnp.float32),
}


@pytest.mark.parametrize("case", list(_SERVED_CASES))
def test_served_paged_attention_matches_oracle(case):
    KVH, n_blocks, window, dtype = _SERVED_CASES[case]
    B, H, D, bs = 4, 4, 8, 4
    first = -1 if window is not None else 5
    tables = jnp.array([[first, 2, 7, -1], [5, 3, -1, -1],
                        [1, 6, -1, -1], [-1, -1, -1, -1]], jnp.int32)
    active = jnp.array([True, True, False, False])
    cur = jnp.array([11, 6, 7, 0], jnp.int32)
    view = fd.paged_view(1, B, tables.shape[1], n_blocks)
    assert view == ("pool" if case.endswith("whole-pool") else "gather")
    # q holds bf16 values in float32, so the outputs compare in float32
    q = _rand(0, (B, H, D)).astype(jnp.bfloat16).astype(jnp.float32)
    k_pool = _rand(1, (n_blocks, bs, KVH, D)).astype(dtype)
    v_pool = _rand(2, (n_blocks, bs, KVH, D)).astype(dtype)
    k_pool = fd.paged_write(k_pool, _rand(3, (B, KVH, D)), tables, cur,
                            active)
    v_pool = fd.paged_write(v_pool, _rand(4, (B, KVH, D)), tables, cur,
                            active)
    want = fd.reference_paged_decode_attention(q, k_pool, v_pool, cur,
                                               tables, 0.25, window=window)
    # the rule's view, then the whole pool where the rule would gather
    # (most of its blocks unreferenced by any table)
    for whole in dict.fromkeys((view == "pool", True)):
        got = jax.jit(functools.partial(
            fd.paged_decode_attention, scale=0.25, window=window,
            whole_pool=whole))(q, k_pool, v_pool, cur, tables)
        assert got.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(got[:3]),
                                   np.asarray(want[:3]),
                                   rtol=2e-4, atol=2e-4)
        if whole:
            # no position to attend: zeros, where the oracle averages
            # garbage
            np.testing.assert_array_equal(np.asarray(got[3]), 0.0)


def test_attend_whole_pool_boundary():
    """The view rule flips where the pool outgrows the blocks the
    tables' slice names: at the chat cell's batch 16 and 256 blocks,
    widths 16 and up read the pool, 8 and below gather; any model axis
    wider than one takes the sharded path."""
    assert fd.paged_view(1, 16, 16, 256) == "pool"       # n == B*C
    assert fd.paged_view(1, 16, 16, 257) == "gather"     # B*C one below
    assert fd.paged_view(1, 16, 16, 255) == "pool"
    assert fd.paged_view(1, 16, 8, 256) == "gather"
    assert fd.paged_view(1, 16, 100, 256) == "pool"
    assert fd.paged_view(1, 1, 1, 2) == "gather"
    assert fd.paged_view(1, 1, 1, 1) == "pool"
    assert fd.paged_view(4, 16, 100, 256) == "sharded"
    assert fd.paged_view(2, 1, 1, 1000) == "sharded"


@pytest.mark.parametrize("n_blocks", [8, 40], ids=["whole-pool", "gathered"])
def test_nonfinite_slot_fails_alone(n_blocks):
    """One slot's hidden state goes NaN in a single-device paged decode
    step (docs/robustness.md, per-request error isolation). Its own
    output is non-finite; the pool stays finite; the other slots'
    outputs equal those of the same step with that slot's state finite;
    and so do theirs on the next step, with the victim retired and its
    blocks left in the pool as they were written."""
    from repro.models import attention
    cfg, params = _setup()
    ap = jax.tree.map(lambda a: a[0], params["backbone"]["layers"]["attn"])
    B, bs = 4, 4
    tables = jnp.array([[5, 2, -1, -1], [5, 3, -1, -1],
                        [1, 6, -1, -1], [0, 4, -1, -1]], jnp.int32)
    assert fd.paged_view(1, B, tables.shape[1], n_blocks) == \
        ("pool" if n_blocks == 8 else "gather")
    shape = (n_blocks, bs, cfg.n_kv_heads, cfg.hd)
    cache = {"k": _rand(1, shape).astype(jnp.bfloat16),
             "v": _rand(2, shape).astype(jnp.bfloat16)}
    cur = jnp.array([6, 7, 5, 8], jnp.int32)
    x = _rand(3, (B, 1, cfg.d_model)).astype(jnp.bfloat16)
    step = jax.jit(lambda x, c, cur, act: attention.decode_attn_step(
        ap, x, c, cur, cfg, active=act, block_tables=tables))
    act = jnp.ones((B,), bool)
    want, c_ok = step(x, cache, cur, act)
    got, c_bad = step(x.at[1].set(jnp.nan), cache, cur, act)
    others = np.array([0, 2, 3])
    assert not np.isfinite(np.asarray(got[1], np.float32)).any()
    for leaf in (c_bad["k"], c_bad["v"]):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(got[others], np.float32),
                                  np.asarray(want[others], np.float32))
    # the next step: the victim is retired, the others write and attend
    act = jnp.array([True, False, True, True])
    cur = cur + act
    x = _rand(4, (B, 1, cfg.d_model)).astype(jnp.bfloat16)
    want, _ = step(x, c_ok, cur, act)
    got, _ = step(x, c_bad, cur, act)
    np.testing.assert_array_equal(np.asarray(got[others], np.float32),
                                  np.asarray(want[others], np.float32))


def test_engine_counts_whole_pool_dispatches_token_identity():
    """A single-device engine whose gather width grows across the rule:
    early dispatches gather, later ones read the whole pool and are
    counted in ``attn_pool_view_dispatches``, and every greedy stream
    matches the solo decode reference."""
    cfg, params = _setup()
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (5, 21)]
    eng = Engine(params, cfg, batch=2, max_len=64, prefill_chunk=8,
                 block_size=4, n_blocks=16)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=10))
    done = []
    while eng.queue or eng.active:
        done.extend(eng.tick())
    whole = eng.counters()["attn_pool_view_dispatches"]
    assert 0 < whole < eng.dispatch_count, (whole, eng.dispatch_count)
    for r in done:
        want = reference_generate(params, cfg, r.prompt, 10, 64)
        assert r.out_tokens == want, (r.rid, r.out_tokens, want)


def test_pow2_bucket_contract():
    """Direct edge-case contract of the one static-arg bucketing rule
    (every static jit width/length goes through it — taxlint TAX002
    sanctions exactly this launderer)."""
    # floor: idle/degenerate demands still compile a width-1 program
    assert pow2_bucket(0, 16) == 1
    assert pow2_bucket(-3, 16) == 1
    assert pow2_bucket(1, 16) == 1
    # interior: smallest power of two >= n
    assert pow2_bucket(2, 16) == 2
    assert pow2_bucket(5, 16) == 8
    assert pow2_bucket(16, 16) == 16
    # ceiling: demands beyond the cap clamp instead of specializing
    assert pow2_bucket(17, 16) == 16
    assert pow2_bucket(10 ** 9, 16) == 16
    # non-pow2 cap is returned as-is when the clamp engages — the top
    # bucket is the exact capacity, never a padded width past it
    assert pow2_bucket(9, 12) == 12
    assert pow2_bucket(3, 12) == 4
    assert pow2_bucket(1, 1) == 1
    assert pow2_bucket(7, 1) == 1
    # monotone non-decreasing in n; bucket count bounded by log2(cap)+1
    cap = 16
    widths = [pow2_bucket(n, cap) for n in range(0, 40)]
    assert widths == sorted(widths)
    assert len(set(widths)) <= cap.bit_length()
    # cap < 1 is a configuration bug: raise, don't return width 0
    for bad_cap in (0, -1):
        try:
            pow2_bucket(4, bad_cap)
        except ValueError:
            pass
        else:
            raise AssertionError(f"cap={bad_cap} must raise")


def test_gather_width_watermark_and_buckets():
    """CachePool.max_blocks_in_use tracks the highest allocated table
    column (holes do NOT lower it — reclaim frees low columns while
    high ones stay live) and gather_width() pads it to power-of-two
    buckets clamped to max_blocks."""
    cfg, params = _setup(n_layers=1)
    pool = CachePool(params, cfg, batch=2, max_len=32, block_size=4)
    assert pool.max_blocks, "smoke cfg must page"
    assert pool.max_blocks_in_use == 0
    assert pool.gather_width() == 1         # floor: never a 0-wide slice
    slot, reused = pool.alloc([1, 2, 3])
    assert pool.writable(slot, 9) == 9      # allocates chunks 0..2
    pool.advance(slot, 9)
    assert pool.max_blocks_in_use == 3
    assert pool.gather_width() == 4         # next power of two
    # window reclaim holes out chunk 0; the high column still governs
    freed = pool.reclaim_out_of_window(slot, 2)
    assert freed == 1 and int(pool.tables[slot, 0]) == -1
    assert pool.max_blocks_in_use == 3
    assert pool.gather_width() == 4
    # grow to the full table: the bucket clamps at max_blocks
    assert pool.writable(slot, 32 - 9) > 0
    assert pool.gather_width() <= pool.max_blocks
    pool.free(slot)
    assert pool.max_blocks_in_use == 0
    m = pool.metrics()
    assert "kv_gather_width" in m and "kv_max_blocks_in_use" in m


def test_preempt_resume_token_identity_with_bucketing():
    """Freshly preempted-then-resumed slot (prefix-hit tables) under
    the live gather-width bucketing: the resumed request's table is
    seeded from registered prefix blocks, the static width tracks the
    watermark, and the stream still matches the solo reference."""
    cfg, params = _setup()
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, 17)]
               for _ in range(2)]
    eng = Engine(params, cfg, batch=2, max_len=64, prefill_chunk=8,
                 block_size=8, n_blocks=6)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=12))
    widths = set()
    done = []
    while eng.queue or eng.active:
        done.extend(eng.tick())
        widths.add(eng.pool.gather_width())
    assert eng.preempt_count >= 1
    assert eng.pool.prefix_hits >= 1        # resume was a prefix hit
    # the watermark actually bit: the engine never needed the full
    # 8-wide table, and bucketing visited more than one specialization
    assert max(widths) < eng.pool.max_blocks, widths
    assert all(w & (w - 1) == 0 for w in widths), widths
    for r in done:
        want = reference_generate(params, cfg, r.prompt, 12, 64)
        assert r.out_tokens == want, (r.rid, r.out_tokens, want)


def test_sliding_window_holes_keep_high_watermark_and_tokens():
    """Sliding-window reclaim punches -1 holes in LIVE tables: the
    gather width must keep covering the high columns while the holes
    are masked, and the stream must match the solo reference."""
    cfg, params = _setup()
    cfgw = cfg.replace(sliding_window=16)
    paramsw = lm.init_params(jax.random.PRNGKey(0), cfgw)
    rng = np.random.default_rng(9)
    prompt = [int(t) for t in rng.integers(1, cfgw.vocab_size, 30)]
    eng = Engine(paramsw, cfgw, batch=2, max_len=64, prefill_chunk=8,
                 block_size=8)
    eng.submit(Request(rid=0, prompt=list(prompt), max_new_tokens=12))
    saw_hole_under_live_high_column = False
    done = []
    while eng.queue or eng.active:
        done.extend(eng.tick())
        t = eng.pool.tables
        if (eng.pool.active[0] and int(t[0, 0]) == -1
                and eng.pool.max_blocks_in_use >= 3):
            saw_hole_under_live_high_column = True
    assert eng.pool.blocks_reclaimed >= 3
    assert saw_hole_under_live_high_column
    want = reference_generate(paramsw, cfgw, prompt, 12, 64)
    assert done[0].out_tokens == want, (done[0].out_tokens, want)


def test_promoted_bounded_bsp_check_8_devices():
    """Per-PR promotion of the bsp-mode bounded-gather distributed
    check: one 8-fake-device subprocess, tiny shapes — the nightly
    battery runs the full mode matrix, this keeps the bounded fused
    region from regressing silently between nightlies."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("from repro.testing import distributed_checks as dc; "
            "dc.check_paged_bounded_gather_bsp_small(); print('OK')")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "OK" in proc.stdout, \
        f"stdout: {proc.stdout[-2000:]}\nstderr: {proc.stderr[-2000:]}"
