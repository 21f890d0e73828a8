"""Ambient distribution context.

The model code needs to know (a) the mesh, (b) the sharding rules, and
(c) the fusion mode for the paper's patterns — without threading them
through every call signature. A small context object with a module-level
current instance keeps the model code readable; the launchers
(train/serve/dryrun/tests) install the context around their jit region.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
from jax.sharding import Mesh

from repro.distributed.sharding_rules import Rules


@dataclasses.dataclass
class DistContext:
    mesh: Mesh
    rules: Rules
    fusion_mode: str = "auto"      # auto | bsp | ring

    @property
    def model_axis_size(self) -> int:
        return self.mesh.shape.get("model", 1)

    @property
    def data_axis_size(self) -> int:
        n = 1
        for a in ("pod", "data"):
            n *= self.mesh.shape.get(a, 1)
        return n


_CURRENT: DistContext | None = None


def single_device_context(fusion_mode: str = "auto") -> DistContext:
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(devices=jax.devices()[:1])
    return DistContext(mesh=mesh, rules=Rules(mesh), fusion_mode=fusion_mode)


def current() -> DistContext:
    global _CURRENT
    if _CURRENT is None:
        _CURRENT = single_device_context()
    return _CURRENT


@contextlib.contextmanager
def use(ctx: DistContext):
    global _CURRENT
    prev = _CURRENT
    _CURRENT = ctx
    try:
        yield ctx
    finally:
        _CURRENT = prev


def make_context(mesh: Mesh, fusion_mode: str = "auto",
                 rules: Rules | None = None) -> DistContext:
    return DistContext(mesh=mesh, rules=rules or Rules(mesh),
                       fusion_mode=fusion_mode)
