"""Multi-device check battery.

Runs under ``--xla_force_host_platform_device_count=N`` in a subprocess
(pytest itself stays single-device per the dry-run hygiene rule). Each
check returns None on success or raises; results are emitted as JSON on
stdout for tests/test_distributed.py to assert on.

Run directly:  python -m repro.testing.run_checks --devices 8
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh

ATOL, RTOL = 2e-4, 2e-4


def _mesh(data=2, model=4):
    return make_mesh((data, model), ("data", "model"))


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


# ----------------------------------------------------------- collective matmul
def check_ag_gemm_k_sharded():
    from repro.core import collective_matmul as cm
    mesh = _mesh()
    a, b = _rand(0, (16, 64)), _rand(1, (64, 32))
    want = a @ b
    a_sh = jax.device_put(a, NamedSharding(mesh, P(None, "model")))
    for mode in ("bsp", "ring", "ring_bidir"):
        got = jax.jit(lambda a, b, m=mode: cm.ag_gemm_k_sharded_sm(
            a, b, mesh, mode=m))(a_sh, b)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def check_ag_gemm_m_sharded():
    from repro.core import collective_matmul as cm
    mesh = _mesh()
    x, w = _rand(0, (2, 16, 64)), _rand(2, (64, 32))
    want = jnp.einsum("bmk,kn->bmn", x, w)
    x_sh = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
    w_sh = jax.device_put(w, NamedSharding(mesh, P(None, "model")))
    for mode in ("bsp", "ring", "ring_bidir"):
        got = jax.jit(lambda a, b, m=mode: cm.ag_gemm_m_sharded_sm(
            a, b, mesh, mode=m))(x_sh, w_sh)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def check_gemm_rs():
    from repro.core import collective_matmul as cm
    mesh = _mesh()
    x, w = _rand(0, (2, 16, 64)), _rand(3, (64, 32))
    want = jnp.einsum("bmk,kn->bmn", x, w)
    x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None, "model")))
    w_sh = jax.device_put(w, NamedSharding(mesh, P("model", None)))
    for mode in ("bsp", "ring", "ring_bidir"):
        got = jax.jit(lambda a, b, m=mode: cm.gemm_rs_sm(
            a, b, mesh, mode=m))(x_sh, w_sh)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def check_all_gather_ring():
    from repro.core import collective_matmul as cm
    import functools
    mesh = _mesh(1, 8)
    x = _rand(0, (8, 4, 128))
    fn = functools.partial(cm.all_gather_ring, axis="model", gather_axis=0)
    got = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("model"),
                                out_specs=P(), axis_names={"model"},
                                check_vma=False))(x)
    np.testing.assert_allclose(got, x, rtol=0, atol=0)


# ------------------------------------------------------------- flash decode
def _strided(k, W):
    B, S = k.shape[0], k.shape[1]
    return (k.reshape(B, S // W, W, *k.shape[2:])
            .swapaxes(1, 2).reshape(k.shape))


def check_flash_decode_modes():
    from repro.core import flash_decode as fd
    mesh = _mesh(2, 4)
    B, H, KVH, D, S, W = 2, 8, 4, 16, 64, 4
    q = _rand(0, (B, H, D))
    k, v = _rand(1, (B, S, KVH, D)), _rand(2, (B, S, KVH, D))
    for cur in (jnp.int32(37), jnp.array([13, 55], jnp.int32)):
        want = fd.reference_decode_attention(q, k, v, cur, 0.25)
        k_sh = jax.device_put(_strided(k, W),
                              NamedSharding(mesh, P(None, "model", None, None)))
        v_sh = jax.device_put(_strided(v, W),
                              NamedSharding(mesh, P(None, "model", None, None)))
        for mode in ("bsp", "ring", "rs_ag"):
            got = jax.jit(lambda q, k, v, c, m=mode: fd.decode_attention_sm(
                q, k, v, c, mesh, scale=0.25, mode=m))(q, k_sh, v_sh, cur)
            np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def check_flash_decode_window():
    from repro.core import flash_decode as fd
    mesh = _mesh(2, 4)
    B, H, KVH, D, S, W = 2, 4, 2, 16, 64, 4
    q, k, v = _rand(0, (B, H, D)), _rand(1, (B, S, KVH, D)), _rand(2, (B, S, KVH, D))
    cur = jnp.int32(49)
    want = fd.reference_decode_attention(q, k, v, cur, 0.25, window=16)
    k_sh = jax.device_put(_strided(k, W),
                          NamedSharding(mesh, P(None, "model", None, None)))
    v_sh = jax.device_put(_strided(v, W),
                          NamedSharding(mesh, P(None, "model", None, None)))
    got = jax.jit(lambda q, k, v, c: fd.decode_attention_sm(
        q, k, v, c, mesh, scale=0.25, mode="ring", window=16))(q, k_sh, v_sh, cur)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


# ------------------------------------------------------------ pallas kernels
def check_pallas_ag_gemm():
    from repro.kernels import ops
    mesh = make_mesh((4,), ("model",))
    M, K, N = 64, 256, 512
    a, b = _rand(0, (M, K)), _rand(1, (K, N))
    a_sh = jax.device_put(a, NamedSharding(mesh, P(None, "model")))
    got = jax.jit(lambda a, b: ops.ag_gemm(a, b, mesh, bn=128))(a_sh, b)
    np.testing.assert_allclose(got, a @ b, rtol=RTOL, atol=ATOL)


def check_pallas_ag_gemm_dtypes():
    from repro.kernels import ops
    mesh = make_mesh((4,), ("model",))
    for dt, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 2e-2)):
        a = _rand(0, (32, 128)).astype(dt)
        b = _rand(1, (128, 256)).astype(dt)
        a_sh = jax.device_put(a, NamedSharding(mesh, P(None, "model")))
        got = jax.jit(lambda a, b: ops.ag_gemm(a, b, mesh, bn=128))(a_sh, b)
        want = (a.astype(jnp.float32) @ b.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=tol, atol=tol * 10)


def check_pallas_flash_decode():
    from repro.kernels import ops, ref
    mesh = make_mesh((4,), ("model",))
    B, H, KVH, D, S, W = 2, 8, 4, 32, 64, 4
    q, k, v = _rand(0, (B, H, D)), _rand(1, (B, S, KVH, D)), _rand(2, (B, S, KVH, D))
    cur = 41
    want = ref.flash_decode_ref(q, k, v, cur, 0.25)
    k_sh = jax.device_put(_strided(k, W),
                          NamedSharding(mesh, P(None, "model", None, None)))
    v_sh = jax.device_put(_strided(v, W),
                          NamedSharding(mesh, P(None, "model", None, None)))
    got = jax.jit(lambda q, k, v, c: ops.flash_decode(
        q, k, v, c, mesh, scale=0.25, blk=16))(q, k_sh, v_sh, cur)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------- model end-to-end
def check_fusion_mode_equivalence():
    """The paper's modes must agree numerically with the BSP baseline."""
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.models import lm
    mesh = _mesh(2, 4)
    # fp32: CPU-XLA CHECK-crashes promoting bf16 all-reduce/reduce-scatter
    # ("copy opcode"); the property under test is algorithmic equivalence
    cfg = smoke_config(get_config("llama3-8b")).replace(
        d_model=128, n_heads=4, n_kv_heads=4, head_dim=32, d_ff=256,
        dtype=jnp.float32)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 64),
                                          0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 64),
                                          0, cfg.vocab_size)}
    losses = {}
    for mode in ("auto", "bsp", "ring"):
        ctx = dctx.make_context(mesh, fusion_mode=mode, rules=Rules(mesh))
        with dctx.use(ctx), mesh:
            loss, _ = jax.jit(lambda p, b: lm.loss_fn(p, b, cfg))(
                params, batch)
            losses[mode] = float(loss)
    base = losses["bsp"]
    for mode, loss in losses.items():
        assert abs(loss - base) < 5e-3, f"{mode} loss {loss} != bsp {base}"


def check_sharded_train_step():
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.launch import steps as steps_lib
    from repro.models import lm
    from repro.optim import adamw
    mesh = _mesh(2, 4)
    cfg = smoke_config(get_config("llama3-8b"))
    rules = Rules(mesh)
    ctx = dctx.make_context(mesh, rules=rules)
    with dctx.use(ctx), mesh:
        psh = steps_lib.param_shardings(cfg, rules)
        params = jax.jit(lambda k: lm.init_params(k, cfg),
                         out_shardings=psh)(jax.random.PRNGKey(0))
        osh = steps_lib.opt_state_shardings(cfg, rules, psh)
        opt_state = jax.jit(adamw.init_state, out_shardings=osh)(params)
        fn = steps_lib.make_train_step(cfg, adamw.AdamWConfig(lr=1e-3))
        jitted = jax.jit(fn, in_shardings=(psh, osh, None),
                         out_shardings=(psh, osh, None))
        batch = {"tokens": jnp.zeros((8, 64), jnp.int32),
                 "labels": jnp.zeros((8, 64), jnp.int32)}
        l0 = None
        for i in range(4):
            params, opt_state, m = jitted(params, opt_state, batch)
            if l0 is None:
                l0 = float(m["loss"])
        assert float(m["loss"]) < l0, "loss did not decrease"


def check_grad_compress_psum():
    import functools
    from repro.distributed import grad_compress as gc
    mesh = _mesh(4, 2)
    g = {"w": _rand(0, (16, 32)), "b": _rand(1, (32,))}

    for scheme in ("bf16", "int8", "none"):
        def body(gg):
            mean, res = gc.compressed_psum_tree(gg, "data", scheme=scheme)
            return mean
        specs = {k: P() for k in g}
        got = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=(specs,), out_specs=specs,
            axis_names={"data"}, check_vma=False))(g)
        tol = {"bf16": 1e-2, "int8": 3e-2, "none": 1e-6}[scheme]
        for k in g:
            np.testing.assert_allclose(got[k], g[k], rtol=tol, atol=tol)


def check_decode_equals_prefill():
    """Decoding token-by-token must match the prefill forward logits."""
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.models import lm
    mesh = _mesh(1, 4)
    for arch in ("llama3-8b", "rwkv6-3b", "zamba2-1.2b"):
        # fp32 so the comparison tests *algorithmic* equivalence, not bf16
        # accumulation-order noise
        cfg = smoke_config(get_config(arch)).replace(remat=False,
                                                     dtype=jnp.float32)
        ctx = dctx.make_context(mesh, rules=Rules(mesh))
        with dctx.use(ctx), mesh:
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            B, S = 2, 16
            toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                      cfg.vocab_size)
            logits_full, _ = jax.jit(
                lambda p, b: lm.forward(p, b, cfg))(
                params, {"tokens": toks})
            state = lm.init_decode_state(params, cfg, B, 32)
            step = jax.jit(lambda p, t, s: lm.decode_step(p, t, s, cfg))
            outs = []
            for t in range(S):
                lg, state = step(params, toks[:, t:t + 1], state)
                outs.append(lg)
            dec = jnp.concatenate(outs, axis=1)
            np.testing.assert_allclose(
                np.asarray(dec, np.float32),
                np.asarray(logits_full, np.float32),
                rtol=5e-2, atol=5e-2)


def check_fused_decode_update():
    """Fused update+attend+combine == XLA-scatter baseline == oracle."""
    from repro.core import flash_decode as fd
    mesh = _mesh(1, 4)
    B, H, KVH, D, S, W = 2, 8, 4, 16, 64, 4
    q = _rand(0, (B, H, D))
    k = _rand(1, (B, S, KVH, D))
    v = _rand(2, (B, S, KVH, D))
    k_new, v_new = _rand(3, (B, KVH, D)), _rand(4, (B, KVH, D))
    for cur in (jnp.int32(38), jnp.array([17, 54], jnp.int32)):
        # oracle: place new kv at position cur-1, attend
        cl = jnp.broadcast_to(jnp.asarray(cur).reshape(-1), (B,))
        k_ref = jax.vmap(lambda kb, nb, p: kb.at[p].set(nb))(k, k_new, cl - 1)
        v_ref = jax.vmap(lambda vb, nb, p: vb.at[p].set(nb))(v, v_new, cl - 1)
        want = fd.reference_decode_attention(q, k_ref, v_ref, cur, 0.25)
        k_sh = jax.device_put(_strided(k, W),
                              NamedSharding(mesh, P(None, "model", None, None)))
        v_sh = jax.device_put(_strided(v, W),
                              NamedSharding(mesh, P(None, "model", None, None)))
        out, ck, cv = jax.jit(
            lambda q, kn, vn, kc, vc, c: fd.decode_attention_fused_sm(
                q, kn, vn, kc, vc, c, mesh, scale=0.25, mode="ring"))(
            q, k_new, v_new, k_sh, v_sh, cur)
        np.testing.assert_allclose(out, want, rtol=RTOL, atol=ATOL)


def check_fused_decode_rolling():
    """Rolling (sliding-window) fused decode matches windowed oracle."""
    from repro.core import flash_decode as fd
    mesh = _mesh(1, 4)
    B, H, KVH, D, S, W = 1, 4, 2, 16, 32, 4   # cache = window = 32
    q = _rand(0, (B, H, D))
    k_new, v_new = _rand(3, (B, KVH, D)), _rand(4, (B, KVH, D))
    # simulate a long stream: cache already full, cur_len = 45 (> S)
    k = _rand(1, (B, S, KVH, D))
    v = _rand(2, (B, S, KVH, D))
    cur = jnp.int32(45)
    # oracle: rolling buffer holds positions 13..44; new token at p=44
    # (slot 44 % 32 = 12). Build the same buffer contents and attend fully.
    p = (45 - 1) % S
    k_roll = k.at[:, p].set(k_new)
    v_roll = v.at[:, p].set(v_new)
    want = fd.reference_decode_attention(q, k_roll, v_roll, jnp.int32(S),
                                         0.25)
    # fused path writes k_new itself; pass the PRE-update cache
    k_pre = jax.device_put(_strided(k, W),
                           NamedSharding(mesh, P(None, "model", None, None)))
    v_pre = jax.device_put(_strided(v, W),
                           NamedSharding(mesh, P(None, "model", None, None)))
    out, _, _ = jax.jit(
        lambda q, kn, vn, kc, vc, c: fd.decode_attention_fused_sm(
            q, kn, vn, kc, vc, c, mesh, scale=0.25, mode="ring",
            rolling_len=S))(q, k_new, v_new, k_pre, v_pre, cur)
    np.testing.assert_allclose(out, want, rtol=RTOL, atol=ATOL)


def check_engine_staggered_admission():
    """THE regression for the per-slot continuous-batching rework:
    requests arriving at different ticks with different prompt lengths,
    admitted mid-run into freed slots, must decode token-for-token what
    a solo run produces — under both the bsp and ring fusion modes
    (ring exercises the fused ownership-aware cache write; chunked
    prefill exercises the per-slot active masking)."""
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.models import lm
    from repro.serving.engine import Engine, Request
    from repro.testing.decode_reference import reference_generate
    cfg = smoke_config(get_config("llama3-8b")).replace(
        n_layers=2, dtype=jnp.float32)
    mesh = _mesh(1, 4)
    prompts = [[1, 2, 3, 4, 5, 6, 7], [3, 4], [5, 6, 9, 11, 13], [9, 8, 7]]
    arrivals = [0, 0, 2, 4]
    for mode in ("bsp", "ring"):
        ctx = dctx.make_context(mesh, fusion_mode=mode, rules=Rules(mesh))
        with dctx.use(ctx), mesh:
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            eng = Engine(params, cfg, batch=2, max_len=64, prefill_chunk=4)
            for i, (p, a) in enumerate(zip(prompts, arrivals)):
                eng.submit(Request(rid=i, prompt=p, max_new_tokens=4),
                           at_tick=a)
            done = eng.run()
            assert len(done) == len(prompts), (mode, len(done))
            for r in done:
                want = reference_generate(params, cfg, r.prompt, 4, 64)
                assert r.out_tokens == want, \
                    (mode, r.rid, r.out_tokens, want)


def check_collective_matmul_validation():
    """The silent-wrong-result shapes must now raise loud ValueErrors."""
    from repro.core import collective_matmul as cm
    mesh = make_mesh((4,), ("model",))

    def expect_raises(fn, frag):
        try:
            fn()
        except ValueError as e:
            assert frag in str(e), (frag, str(e))
            return
        raise AssertionError(f"no ValueError containing {frag!r}")

    # gemm_rs used to DROP rows for M % W != 0
    a, b = _rand(0, (18, 32)), _rand(1, (32, 8))
    expect_raises(lambda: cm.gemm_rs_sm(a, b, mesh), "DROP")
    # ag_gemm_k_sharded ring_bidir mis-slices for odd local K shards
    a2, b2 = _rand(2, (8, 12)), _rand(3, (12, 8))
    expect_raises(
        lambda: cm.ag_gemm_k_sharded_sm(a2, b2, mesh, mode="ring_bidir"),
        "ring_bidir")
    # ragged K sharding
    expect_raises(lambda: cm.ag_gemm_k_sharded_sm(
        _rand(4, (8, 30)), _rand(5, (30, 8)), mesh), "K=30")
    # ag_gemm_m_sharded ragged M
    expect_raises(lambda: cm.ag_gemm_m_sharded_sm(
        _rand(6, (18, 16)), _rand(7, (16, 8)), mesh), "M=18")


def check_pallas_ag_gemm_bn_clamp():
    """ag_gemm_fused with N not a multiple of bn: bn must clamp to a
    divisor of N instead of crashing (the old `assert N % bn == 0`)."""
    from repro.kernels import ops
    mesh = make_mesh((4,), ("model",))
    M, K, N = 32, 256, 384       # N=384 not a multiple of bn=256
    a, b = _rand(0, (M, K)), _rand(1, (K, N))
    a_sh = jax.device_put(a, NamedSharding(mesh, P(None, "model")))
    got = jax.jit(lambda a, b: ops.ag_gemm(a, b, mesh, bn=256))(a_sh, b)
    np.testing.assert_allclose(got, a @ b, rtol=RTOL, atol=ATOL)


def check_paged_flash_decode_modes():
    """Paged (block-table-translated) fused decode == dense oracle on the
    gathered logical view, for every combine schedule, including the
    in-region block write."""
    from repro.core import flash_decode as fd
    mesh = _mesh(1, 4)
    B, H, KVH, D = 2, 8, 4, 16
    bs, n_blocks = 4, 16                    # 4 local blocks per rank
    q = _rand(0, (B, H, D))
    k_pool = _rand(1, (n_blocks, bs, KVH, D))
    v_pool = _rand(2, (n_blocks, bs, KVH, D))
    k_new, v_new = _rand(3, (B, KVH, D)), _rand(4, (B, KVH, D))
    # slot 0: blocks scattered across ranks; slot 1: shares block 9 with
    # slot 0 (prefix sharing) then diverges
    tables = jnp.array([[9, 2, 14, 5, -1, -1],
                        [9, 7, 1, -1, -1, -1]], jnp.int32)
    cur = jnp.array([14, 10], jnp.int32)    # includes this step's token
    # oracle: write at (table[pos//bs], pos%bs) then dense-attend the view
    kp_ref, vp_ref = k_pool, v_pool
    for b in range(B):
        p = int(cur[b]) - 1
        blk = int(tables[b, p // bs])
        kp_ref = kp_ref.at[blk, p % bs].set(k_new[b])
        vp_ref = vp_ref.at[blk, p % bs].set(v_new[b])
    want = fd.reference_paged_decode_attention(q, kp_ref, vp_ref, cur,
                                               tables, 0.25)
    pool_sh = NamedSharding(mesh, P("model", None, None, None))
    for mode in ("bsp", "ring", "rs_ag"):
        out, ck, cv = jax.jit(
            lambda q, kn, vn, kp, vp, c, t, m=mode:
            fd.decode_paged_attention_fused_sm(
                q, kn, vn, kp, vp, c, t, mesh, scale=0.25, mode=m))(
            q, k_new, v_new, jax.device_put(k_pool, pool_sh),
            jax.device_put(v_pool, pool_sh), cur, tables)
        np.testing.assert_allclose(out, want, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(np.asarray(ck), np.asarray(kp_ref),
                                   rtol=0, atol=0)
        np.testing.assert_allclose(np.asarray(cv), np.asarray(vp_ref),
                                   rtol=0, atol=0)


def check_pallas_flash_decode_paged():
    """Fused Pallas kernel with block-table translation == paged oracle."""
    from repro.core import flash_decode as fd
    from repro.kernels import ops
    mesh = make_mesh((4,), ("model",))
    B, H, KVH, D = 2, 8, 4, 32
    bs, n_blocks = 8, 16
    q = _rand(0, (B, H, D))
    k_pool = _rand(1, (n_blocks, bs, KVH, D))
    v_pool = _rand(2, (n_blocks, bs, KVH, D))
    tables = jnp.array([[3, 12, 6, 9],
                        [3, 0, -1, -1]], jnp.int32)   # shared first block
    cur = jnp.array([27, 13], jnp.int32)
    want = fd.reference_paged_decode_attention(q, k_pool, v_pool, cur,
                                               tables, 0.25)
    pool_sh = NamedSharding(mesh, P("model", None, None, None))
    got = jax.jit(lambda q, k, v, c, t: ops.flash_decode_paged(
        q, k, v, c, t, mesh, scale=0.25))(
        q, jax.device_put(k_pool, pool_sh), jax.device_put(v_pool, pool_sh),
        cur, tables)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def check_engine_paged_prefix_sharing():
    """Paged engine under the ring fusion mode: two requests sharing a
    long prompt prefix — the second must record a prefix-cache hit, skip
    re-prefilling the shared span, and still decode exactly the solo-run
    tokens (shared blocks are read-only; divergence happens in private
    blocks)."""
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.models import lm
    from repro.serving.engine import Engine, Request
    from repro.testing.decode_reference import reference_generate
    cfg = smoke_config(get_config("llama3-8b")).replace(
        n_layers=2, dtype=jnp.float32)
    mesh = _mesh(1, 4)
    shared = [7 + (i % 23) for i in range(32)]
    prompts = [shared + [101, 102], shared + [201, 202, 203]]
    ctx = dctx.make_context(mesh, fusion_mode="ring", rules=Rules(mesh))
    with dctx.use(ctx), mesh:
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        eng = Engine(params, cfg, batch=2, max_len=64, prefill_chunk=8,
                     block_size=8)
        eng.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=4))
        # arrives after req 0 finishes prefill: its chunks are registered
        eng.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=4),
                   at_tick=6)
        done = eng.run()
        assert len(done) == 2
        assert eng.pool.prefix_hits >= 1, eng.pool.metrics()
        assert eng.pool.prefix_hit_tokens >= 32, eng.pool.metrics()
        for r in done:
            want = reference_generate(params, cfg, r.prompt, 4, 64)
            assert r.out_tokens == want, (r.rid, r.out_tokens, want)


def check_engine_preemption_token_identity():
    """Block-level preemption under both fusion modes: a KV pool too
    small for the combined decode growth forces every slot to stall —
    the engine must preempt a victim (free its private blocks, fold its
    generated tokens into an effective prompt, re-queue) instead of
    raising, and every request must still decode token-for-token what a
    solo run produces. The ring mode exercises the fused
    ownership-aware paged write on resume; the preempted request's
    registered chunks make the resume a prefix hit."""
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.models import lm
    from repro.serving.engine import Engine, Request
    from repro.testing.decode_reference import reference_generate
    cfg = smoke_config(get_config("llama3-8b")).replace(
        n_layers=2, dtype=jnp.float32)
    mesh = _mesh(1, 4)
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, 17)]
               for _ in range(2)]
    for mode in ("bsp", "ring"):
        ctx = dctx.make_context(mesh, fusion_mode=mode, rules=Rules(mesh))
        with dctx.use(ctx), mesh:
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            # each request's history grows to 17+20-1 = 36 tokens -> 5
            # blocks; two of them need 10 > 8 pool blocks: both stall
            eng = Engine(params, cfg, batch=2, max_len=64,
                         prefill_chunk=8, block_size=8, n_blocks=8)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, prompt=list(p),
                                   max_new_tokens=20))
            done = eng.run()
            assert len(done) == 2, (mode, len(done))
            assert eng.preempt_count >= 1, (mode, eng.preempt_count)
            for r in done:
                want = reference_generate(params, cfg, r.prompt, 20, 64)
                assert r.out_tokens == want, \
                    (mode, r.rid, r.out_tokens, want)


def _paged_hole_oracle(q, k_pool, v_pool, cur, tables, bs, scale,
                       window=None):
    """Dense paged oracle that masks -1 table holes explicitly (the
    shipping reference only masks by cur_len/window, which suffices in
    serving because reclaim holes are always outside the window)."""
    from repro.core import flash_decode as fd
    B, H, D = q.shape
    KVH = k_pool.shape[2]
    g = H // KVH
    C = tables.shape[1]
    kview = np.asarray(fd.gather_paged_view(k_pool, tables), np.float32)
    vview = np.asarray(fd.gather_paged_view(v_pool, tables), np.float32)
    gpos = np.arange(C * bs)
    valid = ((np.asarray(tables) >= 0).repeat(bs, axis=1)
             & (gpos[None, :] < np.asarray(cur)[:, None]))
    if window is not None:
        valid = valid & (gpos[None, :] >= np.asarray(cur)[:, None] - window)
    qf = np.asarray(q, np.float32).reshape(B, KVH, g, D)
    s = np.einsum("bkgd,bksd->bkgs", qf, kview.transpose(0, 2, 1, 3)) * scale
    s = np.where(valid[:, None, None, :], s, np.finfo(np.float32).min)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    o = np.einsum("bkgs,bksd->bkgd", p, vview.transpose(0, 2, 1, 3))
    return o.reshape(B, H, D)


def _paged_bounded_case(modes, *, window=None):
    """Shared body for the bounded-gather raw-op checks: tables with a
    mid-row -1 reclaim hole, cross-shard block scatter, a prefix-shared
    block, and a gather-width leading slice — bounded and masked must
    both match the hole-masking dense oracle, write the same pool
    bytes, and agree with each other to combine-schedule rounding."""
    from repro.core import flash_decode as fd
    mesh = _mesh(1, 4)
    B, H, KVH, D = 2, 8, 4, 16
    bs, n_blocks = 4, 16                    # 4 local blocks per rank
    q = _rand(0, (B, H, D))
    k_pool = _rand(1, (n_blocks, bs, KVH, D))
    v_pool = _rand(2, (n_blocks, bs, KVH, D))
    k_new, v_new = _rand(3, (B, KVH, D)), _rand(4, (B, KVH, D))
    # slot 0: mid-table reclaim hole at chunk 1; slot 1 shares block 9
    tables = jnp.array([[9, -1, 14, 5, -1, -1],
                        [9, 7, 1, -1, -1, -1]], jnp.int32)
    cur = jnp.array([15, 10], jnp.int32)    # includes this step's token
    kp_ref, vp_ref = k_pool, v_pool
    for b in range(B):
        p = int(cur[b]) - 1
        blk = int(tables[b, p // bs])
        assert blk >= 0, "test bug: write position must be allocated"
        kp_ref = kp_ref.at[blk, p % bs].set(k_new[b])
        vp_ref = vp_ref.at[blk, p % bs].set(v_new[b])
    want = _paged_hole_oracle(q, kp_ref, vp_ref, cur, tables, bs, 0.25,
                              window=window)
    pool_sh = NamedSharding(mesh, P("model", None, None, None))
    # width 4 is the tightest slice covering every allocated entry —
    # the serving layer's gather-width bucket for max_blocks_in_use=4
    for width in (tables.shape[1], 4):
        tb = tables[:, :width]
        for mode in modes:
            outs = {}
            for bounded in (True, False):
                out, ck, cv = jax.jit(
                    lambda q, kn, vn, kp, vp, c, t, m=mode, bd=bounded:
                    fd.decode_paged_attention_fused_sm(
                        q, kn, vn, kp, vp, c, t, mesh, scale=0.25,
                        mode=m, window=window, bounded=bd))(
                    q, k_new, v_new, jax.device_put(k_pool, pool_sh),
                    jax.device_put(v_pool, pool_sh), cur, tb)
                np.testing.assert_allclose(out, want, rtol=RTOL, atol=ATOL,
                                           err_msg=f"{mode} bounded="
                                                   f"{bounded} w={width}")
                np.testing.assert_array_equal(np.asarray(ck),
                                              np.asarray(kp_ref))
                np.testing.assert_array_equal(np.asarray(cv),
                                              np.asarray(vp_ref))
                outs[bounded] = np.asarray(out)
            np.testing.assert_allclose(outs[True], outs[False],
                                       rtol=1e-6, atol=1e-6,
                                       err_msg=f"{mode} bounded!=masked")


def check_paged_bounded_vs_masked_modes():
    """Tentpole raw-op oracle: the bounded table-gather paged decode ==
    the masked whole-pool-shard path == the hole-masking dense oracle,
    for every combine schedule, with reclaim holes, a sliding window,
    and a gather-width leading slice."""
    _paged_bounded_case(("bsp", "ring", "rs_ag"))
    _paged_bounded_case(("ring",), window=6)


def check_paged_bounded_gather_bsp_small():
    """Fast-tier promotion (per-PR): the bsp-mode slice of the bounded
    raw-op check at the same tiny shapes — keeps the bounded gather
    from regressing silently between nightly battery runs."""
    _paged_bounded_case(("bsp",))


def check_engine_bounded_token_identity():
    """Tentpole end-to-end oracle: bounded table-gather vs masked-pool
    engines must decode TOKEN-IDENTICAL streams under bsp and ring —
    including after preemption re-admits a victim on prefix-hit tables
    (pool too small for combined growth) and, under ring, after
    sliding-window reclaim leaves -1 holes in live tables. The masked
    path is the PR-2/PR-3 regression anchor, so identity to it carries
    identity to the solo-run reference."""
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.models import lm
    from repro.serving.engine import Engine, Request
    cfg = smoke_config(get_config("llama3-8b")).replace(
        n_layers=2, dtype=jnp.float32)
    mesh = _mesh(1, 4)
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, 17)]
               for _ in range(2)]
    wprompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 30)]
    for mode in ("bsp", "ring"):
        ctx = dctx.make_context(mesh, fusion_mode=mode, rules=Rules(mesh))
        with dctx.use(ctx), mesh:
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            streams = {}
            for bounded in (True, False):
                eng = Engine(params, cfg, batch=2, max_len=64,
                             prefill_chunk=8, block_size=8, n_blocks=8,
                             bounded_gather=bounded)
                for i, p in enumerate(prompts):
                    eng.submit(Request(rid=i, prompt=list(p),
                                       max_new_tokens=20))
                done = eng.run()
                assert len(done) == 2, (mode, bounded, len(done))
                assert eng.preempt_count >= 1, (mode, bounded)
                streams[bounded] = {r.rid: r.out_tokens for r in done}
            assert streams[True] == streams[False], (mode, streams)
            if mode != "ring":
                continue
            # sliding-window reclaim holes (ring = fused paged write)
            cfgw = cfg.replace(sliding_window=16)
            paramsw = lm.init_params(jax.random.PRNGKey(0), cfgw)
            wstreams = {}
            for bounded in (True, False):
                eng = Engine(paramsw, cfgw, batch=2, max_len=64,
                             prefill_chunk=8, block_size=8,
                             bounded_gather=bounded)
                eng.submit(Request(rid=0, prompt=list(wprompt),
                                   max_new_tokens=12))
                done = eng.run()
                assert eng.pool.blocks_reclaimed >= 3, (mode, bounded)
                wstreams[bounded] = done[0].out_tokens
            assert wstreams[True] == wstreams[False], (mode, wstreams)


def _engine_megatick_case(mode, *, samplers=("greedy", "temperature"),
                          window=True):
    """Shared body for the megatick identity checks: K=8 megatick
    engines vs the K=1 single-step anchor under one fusion mode —
    through preemption (pool too small for combined growth) and,
    optionally, sliding-window reclaim holes punched at megatick
    boundaries."""
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.models import lm
    from repro.serving.engine import Engine, Request
    cfg = smoke_config(get_config("llama3-8b")).replace(
        n_layers=2, dtype=jnp.float32)
    mesh = _mesh(1, 4)
    rng = np.random.default_rng(13)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, 9)]
               for _ in range(2)]
    wprompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 30)]
    ctx = dctx.make_context(mesh, fusion_mode=mode, rules=Rules(mesh))
    with dctx.use(ctx), mesh:
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        for sampler in samplers:
            streams = {}
            for K in (1, 8):
                # 9 + 12 tokens -> 3 blocks/slot, 4-block pool: the
                # engines must preempt, and the megatick engine must
                # do it at a megatick boundary
                eng = Engine(params, cfg, batch=2, max_len=64,
                             prefill_chunk=8, block_size=8, n_blocks=4,
                             sampler=sampler, seed=7, decode_steps=K)
                for i, p in enumerate(prompts):
                    eng.submit(Request(rid=i, prompt=list(p),
                                       max_new_tokens=12, temp=1.0))
                done = eng.run()
                assert len(done) == 2, (mode, sampler, K, len(done))
                assert eng.preempt_count >= 1, (mode, sampler, K)
                streams[K] = {r.rid: r.out_tokens for r in done}
            assert streams[1] == streams[8], (mode, sampler, streams)
        if not window:
            return
        # sliding-window reclaim holes punched at megatick boundaries
        cfgw = cfg.replace(sliding_window=16)
        paramsw = lm.init_params(jax.random.PRNGKey(0), cfgw)
        wstreams = {}
        for K in (1, 8):
            eng = Engine(paramsw, cfgw, batch=2, max_len=64,
                         prefill_chunk=8, block_size=8, decode_steps=K)
            eng.submit(Request(rid=0, prompt=list(wprompt),
                               max_new_tokens=12))
            done = eng.run()
            assert eng.pool.blocks_reclaimed >= 3, (mode, K)
            wstreams[K] = done[0].out_tokens
        assert wstreams[1] == wstreams[8], (mode, wstreams)


def check_engine_megatick_token_identity():
    """Megatick tentpole oracle: ``Engine(decode_steps=8)`` — one fused
    jitted program per 8 decode steps with DEVICE-RESIDENT sampling —
    must decode TOKEN-IDENTICAL streams to the single-step engine
    under bsp and ring, for greedy and the seeded temperature sampler,
    including through preemption and sliding-window reclaim. The
    single-step engine is the PR-1..4 regression anchor, so identity
    to it carries identity to the solo-run reference."""
    for mode in ("bsp", "ring"):
        _engine_megatick_case(mode)


def check_engine_megatick_bsp_small():
    """Per-PR promotable subset of the megatick identity check: bsp
    only, greedy only, no window leg — small enough for the fast
    tier's 8-fake-device subprocess (the nightly battery runs the full
    mode x sampler x window matrix above)."""
    _engine_megatick_case("bsp", samplers=("greedy",), window=False)


def _engine_mixed_megatick_case(mode, *, samplers=("greedy",
                                                   "temperature"),
                                window=True):
    """Shared body for the MIXED megatick identity checks: K=4 engines
    under STAGGERED arrivals — prefill in flight for most of the run,
    so every fused dispatch is the mixed prefill+decode program
    (``lm.decode_mixed``), never the pure-decode fast path alone — vs
    the K=1 single-step anchor. Covers mid-megatick prefill->decode
    transitions, preemption at megatick boundaries, and (optionally)
    sliding-window reclaim."""
    from repro.configs import get_config, smoke_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import Rules
    from repro.models import lm
    from repro.serving.engine import Engine, Request
    cfg = smoke_config(get_config("llama3-8b")).replace(
        n_layers=2, dtype=jnp.float32)
    mesh = _mesh(1, 4)
    rng = np.random.default_rng(29)
    # every request outgrows 2 blocks (prompt + 11 written KV > 16
    # tokens; the final sampled token's write is deferred), so two
    # co-resident slots exhaust the 4-block pool and MUST preempt
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (9, 6, 12)]
    wprompt = [int(t) for t in rng.integers(1, cfg.vocab_size, 30)]
    ctx = dctx.make_context(mesh, fusion_mode=mode, rules=Rules(mesh))
    with dctx.use(ctx), mesh:
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        for sampler in samplers:
            streams = {}
            for K in (1, 4):
                # staggered arrivals + a pool too small for combined
                # growth: new prompts keep prefill in flight while
                # earlier slots decode, and the engines must preempt
                eng = Engine(params, cfg, batch=2, max_len=64,
                             prefill_chunk=8, block_size=8, n_blocks=4,
                             sampler=sampler, seed=7, decode_steps=K)
                for i, p in enumerate(prompts):
                    eng.submit(Request(rid=i, prompt=list(p),
                                       max_new_tokens=12, temp=1.0),
                               at_tick=2 * i)
                done = eng.run()
                assert len(done) == 3, (mode, sampler, K, len(done))
                assert eng.preempt_count >= 1, (mode, sampler, K)
                if K > 1:
                    assert eng.mixed_dispatch_count > 0, (mode, sampler)
                    assert eng.mixed_prompt_token_count > 0, \
                        (mode, sampler)
                streams[K] = {r.rid: r.out_tokens for r in done}
            assert streams[1] == streams[4], (mode, sampler, streams)
        if not window:
            return
        # sliding-window reclaim holes punched at mixed-megatick
        # boundaries (the long prompt keeps the slot prefilling across
        # several megaticks before decode takes over mid-dispatch)
        cfgw = cfg.replace(sliding_window=16)
        paramsw = lm.init_params(jax.random.PRNGKey(0), cfgw)
        wstreams = {}
        for K in (1, 4):
            eng = Engine(paramsw, cfgw, batch=2, max_len=64,
                         prefill_chunk=8, block_size=8, decode_steps=K)
            eng.submit(Request(rid=0, prompt=list(wprompt),
                               max_new_tokens=12))
            done = eng.run()
            assert eng.pool.blocks_reclaimed >= 3, (mode, K)
            if K > 1:
                assert eng.mixed_dispatch_count > 0, mode
            wstreams[K] = done[0].out_tokens
        assert wstreams[1] == wstreams[4], (mode, wstreams)


def check_engine_mixed_megatick_token_identity():
    """Mixed-megatick tentpole oracle: ``Engine(decode_steps=4)`` under
    staggered arrivals — prompt chunks piggybacking on the fused decode
    scan (``lm.decode_mixed``), first token sampled at the step that
    consumes the last prompt token — must decode TOKEN-IDENTICAL
    streams to the single-step engine under bsp and ring, for greedy
    and the seeded temperature sampler, through preemption and
    sliding-window reclaim."""
    for mode in ("bsp", "ring"):
        _engine_mixed_megatick_case(mode)


def check_engine_mixed_megatick_bsp_small():
    """Per-PR promotable subset of the mixed-megatick identity check:
    bsp only, greedy only, no window leg — small enough for the fast
    tier's 8-fake-device subprocess (the nightly battery runs the full
    mode x sampler x window matrix above)."""
    _engine_mixed_megatick_case("bsp", samplers=("greedy",),
                                window=False)


# keep LAST so every check_* above is collected (a mid-file listing
# silently dropped later checks from the battery)
ALL_CHECKS = [v for k, v in sorted(globals().items())
              if k.startswith("check_")]
