"""Logical-axis sharding: the bridge between model code and the mesh.

Model init functions tag every parameter leaf with *logical* axis names
(``tag(value, "embed", "mlp")``). A ``ShardingRules`` table maps logical
names to physical mesh axes (or None = replicated). This keeps model code
mesh-agnostic: the same model runs on (16,16) ``("data","model")``,
(2,16,16) ``("pod","data","model")``, or a 1-device CPU mesh, purely by
swapping rules — the MaxText/Flax "logical axis" pattern, dependency-free.

Physical mapping (defaults):
  batch    -> ("pod", "data")   data parallel over pods x pod-local DP
  embed    -> "data"            FSDP: weights sharded over DP, gathered on use
                                (replicated across pods: cross-DCN ZeRO-3 is
                                not worth the DCN all-gathers)
  heads/kv_heads/mlp/vocab/expert -> "model"   tensor / expert parallelism
  seq      -> None (or "model" for context-parallel attention configs)

Rules are plain dicts so per-arch overrides are one-line diffs; unknown
logical names map to None (replicated) loudly via ``strict``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[str, Sequence[str], None]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Param:
    """A parameter value tagged with logical axis names (one per dim)."""
    value: Any
    axes: tuple

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)


def tag(value, *axes) -> Param:
    if hasattr(value, "ndim") and len(axes) != value.ndim:
        raise ValueError(f"axes {axes} do not match value ndim {value.ndim}")
    return Param(value, tuple(axes))


def _is_param(x):
    return isinstance(x, Param)


def unzip(tree):
    """Split a Param-tagged tree into (values_tree, axes_tree).

    Untagged leaves (models without sharding annotations, e.g. the paper's
    RNN families) pass through with all-None axes, i.e. replicated."""
    values = jax.tree.map(lambda p: p.value if _is_param(p) else p, tree,
                          is_leaf=_is_param)
    axes = jax.tree.map(
        lambda p: p.axes if _is_param(p)
        else (None,) * getattr(p, "ndim", 0), tree, is_leaf=_is_param)
    return values, axes


def strip(tree):
    """Values only (CPU tests / places that don't care about sharding)."""
    return jax.tree.map(lambda p: p.value if _is_param(p) else p, tree,
                        is_leaf=_is_param)


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------

DEFAULT_RULES: dict = {
    "batch": ("pod", "data"),
    "seq": None,            # "model" enables context-parallel attention
    "kv_seq": None,
    "embed": "data",        # FSDP axis for weights
    "embed_act": None,      # activation d_model dim
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": None,         # "model" enables expert parallelism
    "expert_mlp": "model",
    "layer": None,
    "state": None,
    "conv": None,
    "norm": None,
    "cap": None,            # MoE capacity dim
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: Mapping[str, Axes]
    mesh_axes: tuple = ("data", "model")

    def resolve(self, name: Optional[str]) -> Axes:
        if name is None:
            return None
        ax = self.rules.get(name, None)
        # Drop mesh axes the current mesh doesn't have (e.g. "pod" on 2D mesh).
        if isinstance(ax, str):
            return ax if ax in self.mesh_axes else None
        if isinstance(ax, (tuple, list)):
            kept = tuple(a for a in ax if a in self.mesh_axes)
            return kept if kept else None
        return None

    def with_(self, **overrides) -> "ShardingRules":
        return ShardingRules({**self.rules, **overrides}, self.mesh_axes)


def rules_for_mesh(mesh: Mesh, overrides: Optional[dict] = None) -> ShardingRules:
    r = dict(DEFAULT_RULES)
    if overrides:
        r.update(overrides)
    return ShardingRules(r, tuple(mesh.axis_names))


def logical_to_pspec(axes: Sequence[Optional[str]], rules: ShardingRules,
                     shape: Optional[Sequence[int]] = None,
                     mesh: Optional[Mesh] = None) -> P:
    """Map logical axis names to a PartitionSpec, dropping non-divisible axes.

    Divisibility guard: a logical axis whose dim isn't divisible by the mesh
    axis size falls back to replication (e.g. 40 heads on a 16-way "model"
    axis). This makes every config lower cleanly; the roofline then exposes
    the cost of replication, which is the honest signal to hillclimb on.
    """
    parts = []
    used: set = set()
    for i, name in enumerate(axes):
        ax = rules.resolve(name)
        if ax is not None and shape is not None and mesh is not None:
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= mesh.shape[a]
            if shape[i] % size != 0:
                ax = None
        # a mesh axis may appear at most once per spec: first dim wins
        # (e.g. ("mlp","heads") both -> "model" on fused in/out projections)
        if ax is not None:
            flat = ax if isinstance(ax, tuple) else (ax,)
            if any(a in used for a in flat):
                ax = None
            else:
                used.update(flat)
        parts.append(ax)
    # PartitionSpec with trailing Nones trimmed is equivalent; keep full rank.
    return P(*parts)


def make_shardings(axes_tree, rules: ShardingRules, mesh: Mesh,
                   shapes_tree=None):
    """NamedSharding tree from a logical-axes tree (+ optional shapes tree)."""
    def is_axes(x):
        return isinstance(x, tuple) and all(isinstance(a, (str, type(None)))
                                            for a in x)

    if shapes_tree is None:
        return jax.tree.map(
            lambda ax: NamedSharding(mesh, logical_to_pspec(ax, rules)),
            axes_tree, is_leaf=is_axes)
    return jax.tree.map(
        lambda ax, s: NamedSharding(
            mesh, logical_to_pspec(ax, rules, getattr(s, "shape", None), mesh)),
        axes_tree, shapes_tree, is_leaf=is_axes)


def shard_put(tree, axes_tree, rules: ShardingRules, mesh: Mesh):
    """device_put a VALUE tree onto the mesh by its logical-axes tree
    (divisibility-guarded: non-divisible dims replicate). Used to place
    serving decode state — batch/slots over ("pod","data"), kv-heads over
    "model" — without the values ever living unsharded on one device."""
    return jax.device_put(tree, make_shardings(axes_tree, rules, mesh, tree))


def shard_act(x: jax.Array, axes: Sequence[Optional[str]],
              rules: Optional[ShardingRules]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op when rules is None
    (CPU tests) or when no mesh is set (``jax.set_mesh``).

    Inside ``shard_map`` the mapped axes are Manual: ``x`` is already this
    shard's slice along them, so only the mesh's Auto axes are constrained
    (none at all when every axis is mapped)."""
    if rules is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.are_all_axes_manual:
        return x
    if mesh.manual_axes:
        rules = dataclasses.replace(rules, mesh_axes=tuple(
            a for a in rules.mesh_axes if a not in mesh.manual_axes))
    spec = logical_to_pspec(axes, rules, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)
