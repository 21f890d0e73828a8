"""Mesh construction.

(16, 16) = one v5e pod (256 chips): axes (data, model).
(2, 16, 16) = two pods (512 chips): axes (pod, data, model) — DP across
pods, FSDP on `data`, TP/SP/EP on `model`.

Functions (not module constants) so importing never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices *before* calling.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """The one mesh constructor: ``jax.make_mesh`` with every axis
    ``Auto``. jax >= 0.7 defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` and the sharding rules' logical specs
    are refused; the model code is written for XLA-SPMD propagation."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh_for_devices(n: int, model: int = 16, pods: int = 1):
    """Elastic variant: whatever chip count we actually have."""
    assert n % (model * pods) == 0
    data = n // (model * pods)
    if pods > 1:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def make_host_mesh(data: int = 1, model: int = 1, devices=None):
    """(data, model) mesh over local (possibly fake) devices, or over
    ``devices`` when given."""
    return make_mesh((data, model), ("data", "model"), devices=devices)
