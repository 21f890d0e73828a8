"""Logical-axis -> mesh-axis sharding rules (MaxText-style).

Params and activations carry *logical* axis names ("embed", "mlp",
"heads", ...). A :class:`Rules` object maps those to mesh axes, with a
divisibility fallback: if a logical dim is not divisible by the mesh axes
it would map to, the mapping silently degrades to replication for that
tensor axis (recorded, so the dry-run can report degradations). This is
what lets e.g. paligemma (8 heads) run on a model=16 mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Default logical->mesh mapping. "fsdp" shards params over the data axis
# (ZeRO-3 style); the pod axis is pure DP (params replicated across pods)
# unless a rule lists it explicitly.
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    # params
    "vocab": ("model",),
    "in_vocab": ("data",),       # input embed storage rows (FSDP)
    "in_embed": ("model",),      # input embed cols (gather stays local)
    "embed": ("data",),          # fsdp axis for the embedding/residual dim
    "embed_no_fsdp": (),
    "mlp": ("model",),           # d_ff tensor-parallel
    "heads": ("model",),         # attention heads tensor-parallel
    "kv_heads": ("model",),
    "head_dim": (),
    "qkv": ("model",),           # fused qkv output dim
    "experts": ("model",),       # expert parallelism
    "expert_mlp": (),            # per-expert d_ff (used when experts < model)
    "layers": (),                # scan-stacked layer dim
    "ssm_inner": ("model",),
    "ssm_state": (),
    "conv_width": (),
    # activations
    "batch": ("pod", "data"),
    "seq": ("model",),           # sequence parallelism between blocks
    "kv_seq": ("model",),        # decode KV cache sequence sharding
    "kv_blocks": ("model",),     # paged KV pool block dim (serving)
    "act_embed": (),
    "act_mlp": ("model",),
    "act_heads": ("model",),
}


@dataclasses.dataclass
class Rules:
    mesh: Mesh
    table: Mapping[str, tuple[str, ...]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES))
    degradations: list[str] = dataclasses.field(default_factory=list)

    def _mesh_size(self, mesh_axes: tuple[str, ...]) -> int:
        n = 1
        for a in mesh_axes:
            n *= self.mesh.shape.get(a, 1)
        return n

    def spec_for(self, logical_axes: Sequence[str | None],
                 shape: Sequence[int] | None = None,
                 name: str = "") -> P:
        """PartitionSpec for one tensor, applying divisibility fallback."""
        parts = []
        for i, ax in enumerate(logical_axes):
            if ax is None or ax not in self.table:
                parts.append(None)
                continue
            mesh_axes = tuple(a for a in self.table[ax]
                              if self.mesh.shape.get(a, 1) > 1)
            if not mesh_axes:
                parts.append(None)
                continue
            if shape is not None:
                n = self._mesh_size(mesh_axes)
                if shape[i] % n != 0:
                    self.degradations.append(
                        f"{name or 'tensor'} axis {i} ({ax}={shape[i]}) not "
                        f"divisible by mesh {mesh_axes} ({n}) -> replicated")
                    parts.append(None)
                    continue
            parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
        # PartitionSpec must not repeat a mesh axis; later occurrences degrade.
        seen: set[str] = set()
        clean = []
        for p in parts:
            axes = (p,) if isinstance(p, str) else (p or ())
            if any(a in seen for a in axes):
                clean.append(None)
                continue
            seen.update(axes)
            clean.append(p)
        return P(*clean)

    def tree_specs(self, axes_tree, shapes_tree=None):
        """Map an axes tree (+ optional shapes tree) to PartitionSpecs."""
        if shapes_tree is None:
            return jax.tree.map(
                lambda ax: self.spec_for(ax) if ax is not None else P(),
                axes_tree, is_leaf=lambda x: isinstance(x, tuple) or x is None)
        return jax.tree.map(
            lambda ax, sh: (self.spec_for(ax, getattr(sh, "shape", sh))
                            if ax is not None else P()),
            axes_tree, shapes_tree,
            is_leaf=lambda x: isinstance(x, tuple) or x is None)

    def shardings(self, axes_tree, shapes_tree=None):
        specs = self.tree_specs(axes_tree, shapes_tree)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))


def rules_for(cfg, mesh: Mesh) -> Rules:
    """Rules with per-arch overrides applied (hillclimbed per
    EXPERIMENTS.md §Perf — e.g. olmoe replicates expert weights over
    `model` because moving weights beats moving top-8 token activations)."""
    table = dict(DEFAULT_RULES)
    for k, v in (getattr(cfg, "sharding_overrides", ()) or ()):
        table[k] = tuple(v)
    return Rules(mesh, table=table)


def constrain(x, rules: Rules, *logical_axes: str | None):
    """with_sharding_constraint by logical axis names (no-op off-mesh)."""
    if rules is None:
        return x
    spec = rules.spec_for(logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))
