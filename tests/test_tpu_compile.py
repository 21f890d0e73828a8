"""Compile the served megatick programs for a described TPU v5e.

Nothing runs: ``topologies.get_topology_desc`` describes a v5e 2x2 host
and the TPU compiler, which is installed here, compiles for it. This
catches what interpret mode and the CPU backend cannot: tiling and
fast-memory refusals, partitioning failures, programs the chip refuses.

phi3-mini-3.8b at its published widths with depth cut to 2 layers; the
programs are the mixed and the pure megatick as
``Engine._build_dispatchers`` builds them, on one described chip and
on the 2x2 mesh at TP=4 under the ``auto`` and ``ring`` fusion modes.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest's workers all
import every test file.
"""
import os
import re

import pytest

B, MAX_LEN, BLOCK, K, M = 8, 256, 16, 4, 8


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(topo, tp: int, mode: str, program: str, gw: int = 8):
    """Lower + compile one megatick program for ``tp`` described chips
    at static gather width ``gw``. Returns the compiled executable."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.distributed import context as dctx
    from repro.distributed.sharding_rules import rules_for
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.models.module import axes_tree, shapes_tree
    from repro.serving.engine import Engine

    cfg = get_config("phi3-mini-3.8b").replace(n_layers=2)
    cfg = cfg.replace(param_dtype=cfg.dtype)
    mesh = make_host_mesh(data=1, model=tp, devices=topo.devices[:tp])
    rules = rules_for(cfg, mesh)
    ctx = dctx.make_context(mesh, fusion_mode=mode, rules=rules)

    def placed(shapes, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            shapes, shardings)

    spec = lm.lm_spec(cfg)
    params = placed(shapes_tree(spec),
                    rules.shardings(axes_tree(spec), shapes_tree(spec)))
    max_blocks = MAX_LEN // BLOCK
    state = jax.eval_shape(lambda: lm.init_paged_decode_state(
        None, cfg, B, B * max_blocks, BLOCK, max_blocks))
    state = placed(state, rules.shardings(lm.paged_state_axes(state),
                                          state))
    rep = NamedSharding(mesh, P())

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    # the engine's dispatchers without an engine: building one would
    # allocate its pool on the host's devices
    eng = Engine.__new__(Engine)
    eng.cfg, eng.sampler, eng.bounded_gather = cfg, "greedy", True
    eng.ctx = ctx
    eng._base_key = jax.random.PRNGKey(0)
    eng._build_dispatchers()
    f32 = jnp.float32
    if program == "mixed":
        S = M
        lowered = eng._stepM.lower(
            params, arr((B, S)), arr((B, 1)), arr((B,)), arr((B,)),
            arr((B,)), state, arr((B,)), arr((B,)), arr((B,), f32),
            arr((B,)), S, gw)
    else:
        lowered = eng._stepK.lower(
            params, arr((B, 1)), arr((B,)), state, arr((B,)), arr((B,)),
            arr((B,), f32), arr((B,)), K, gw)
    return lowered.compile()


@pytest.mark.parametrize("program", ["mixed", "pure"])
@pytest.mark.parametrize("tp,mode", [(1, "auto"), (4, "auto"),
                                     (4, "ring")])
def test_megatick_compiles_for_v5e(topo, tp, mode, program):
    compiled = _compile(topo, tp, mode, program)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    hlo = compiled.as_text()
    if tp == 1:
        assert "all-reduce" not in hlo and "collective-permute" not in hlo
    elif mode == "ring":
        # the ring combine / collective matmul hop between neighbours
        assert "collective-permute" in hlo
    else:
        assert "all-reduce" in hlo or "reduce-scatter" in hlo


@pytest.mark.parametrize("gw", [4, 16])
def test_single_chip_attention_reads_kv_in_bf16(topo, gw):
    """One chip, on both sides of ``flash_decode.paged_view`` (the pool
    holds B x 16 blocks: width 16 reads the whole pool, 4 gathers). A
    float32 array of KV rows (a dim of head_dim and at least a block per
    slot of elements) is what a widened view looks like. The whole-pool
    program has none: the pool is read as stored. The gathered program
    has it: its per-slot matvec becomes a float32 multiply-reduce behind
    a convert of the view, which is why that side keeps the oracle."""
    from repro.configs import get_config
    from repro.core.flash_decode import paged_view

    view = paged_view(1, B, gw, B * MAX_LEN // BLOCK)
    assert view == ("pool" if gw == 16 else "gather")
    cfg = get_config("phi3-mini-3.8b")
    kv_rows = B * BLOCK * cfg.n_kv_heads * cfg.hd
    widened = []
    for m in re.finditer(r"= f32\[([0-9,]+)\]",
                         _compile(topo, 1, "auto", "mixed", gw).as_text()):
        dims = [int(d) for d in m.group(1).split(",")]
        n = 1
        for d in dims:
            n *= d
        if cfg.hd in dims and n >= kv_rows:
            widened.append(m.group(0))
    if view == "pool":
        assert not widened, sorted(set(widened))
    else:
        assert widened
