"""Checkpointing: roundtrip, atomicity, retention, async, elasticity."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.launch.mesh import make_mesh


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 16)),
                       "b": jnp.zeros((16,))},
            "opt": {"m": jnp.ones((3,)), "step": jnp.int32(7)}}


def test_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=False)
    t = _tree()
    ck.save(10, t, extra={"next_step": 10})
    restored, manifest = ck.restore(None, jax.tree.map(jnp.zeros_like, t))
    assert manifest["step"] == 10
    assert manifest["extra"]["next_step"] == 10
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_then_restore(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=True)
    t = _tree(1)
    ck.save(5, t)
    ck.wait()
    restored, m = ck.restore(5, jax.tree.map(jnp.zeros_like, t))
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(t["params"]["w"]))


def test_retention_gc(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    t = _tree()
    for s in (1, 2, 3, 4):
        ck.save(s, t)
    assert ck.all_steps() == [3, 4]


def test_no_partial_checkpoint_visible(tmp_path):
    """A crash mid-write (simulated by a stray tmp dir) is never listed."""
    ck = Checkpointer(str(tmp_path), async_save=False)
    os.makedirs(tmp_path / ".tmp_step_9_12345")
    ck.save(1, _tree())
    assert ck.all_steps() == [1]
    # manifest must exist for a step to count
    os.makedirs(tmp_path / "step_00000099")
    assert ck.all_steps() == [1]


def test_restore_latest_picks_max(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=False, keep=10)
    for s in (3, 11, 7):
        ck.save(s, _tree(s))
    assert ck.latest_step() == 11


def test_restore_missing_leaf_raises(tmp_path):
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save(1, {"a": jnp.ones((2,))})
    with pytest.raises(KeyError):
        ck.restore(1, {"a": jnp.ones((2,)), "extra": jnp.ones((3,))})


def test_elastic_restore_resharding(tmp_path):
    """Restore with explicit shardings device_puts onto the current mesh
    (single device here, but exercises the code path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1,), ("data",))
    ck = Checkpointer(str(tmp_path), async_save=False)
    t = _tree(2)
    ck.save(1, t)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    restored, _ = ck.restore(1, jax.tree.map(jnp.zeros_like, t),
                             shardings=sh)
    assert restored["params"]["w"].sharding == NamedSharding(mesh, P())
