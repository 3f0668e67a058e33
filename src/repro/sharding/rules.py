"""Logical-axis sharding rules (MaxText-style) for the whole model zoo.

Every parameter is annotated at init time with a tuple of *logical* axis
names; a rules table maps logical axes to mesh axes. One table drives TP,
EP, SP and DP for all ten architectures, and the perf hillclimb mutates the
table instead of the model code.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

MeshAxes = Union[None, str, Tuple[str, ...]]

# Default rules: Megatron-style TP on 'model', DP over ('pod','data').
DEFAULT_RULES: Dict[str, MeshAxes] = {
    # weights
    "embed": None,               # d_model dim of weights: replicated
    "mlp": "model",              # FFN hidden
    "heads": "model",            # attention heads (fused q dim)
    "kv_heads": "model",         # KV heads (GQA; uneven sizes padded by GSPMD)
    "head_dim": None,
    "vocab": "model",            # embedding/output vocab dim
    "expert": "model",           # MoE expert dim (EP)
    "expert_mlp": None,
    "kv_lora": None,             # MLA compression dim
    "ssm_inner": "model",        # Mamba d_inner / heads
    "ssm_state": None,
    "conv": None,
    "layers": None,              # stacked scan dim: always replicated
    "qblocks": ("data", "model"),  # int8 optimizer moment blocks (ZeRO)
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,              # KV-cache seq dim (SP shards this for 500k)
    "act_embed": None,
    "act_heads": "model",
    "groups": ("pod", "data"),   # MoE dispatch groups
    "expert_cap": None,
}


@dataclasses.dataclass
class ShardingRules:
    rules: Dict[str, MeshAxes]

    def __hash__(self):
        # treated as immutable everywhere (with_overrides/for_mesh build
        # new instances); hashable so jitted-entry-point factories can
        # lru-cache on (cfg, rules, ...) instead of retracing per call
        return hash(tuple(sorted(self.rules.items())))

    def spec(self, logical_axes: Optional[Tuple[Optional[str], ...]]) -> PartitionSpec:
        if logical_axes is None:
            return PartitionSpec()
        out = []
        for ax in logical_axes:
            r = self.rules.get(ax) if ax is not None else None
            out.append(r)
        return PartitionSpec(*out)

    def with_overrides(self, **kv) -> "ShardingRules":
        d = dict(self.rules)
        d.update(kv)
        return ShardingRules(d)

    def for_mesh(self, mesh: Mesh) -> "ShardingRules":
        """Drop mesh axes that don't exist in `mesh` (e.g. 'pod' on the
        single-pod mesh) from every rule."""
        names = set(mesh.axis_names)

        def fit(v: MeshAxes) -> MeshAxes:
            if v is None:
                return None
            if isinstance(v, str):
                return v if v in names else None
            kept = tuple(a for a in v if a in names)
            if not kept:
                return None
            return kept[0] if len(kept) == 1 else kept

        return ShardingRules({k: fit(v) for k, v in self.rules.items()})


def default_rules(**overrides) -> ShardingRules:
    return ShardingRules(dict(DEFAULT_RULES)).with_overrides(**overrides)


def tree_specs(rules: ShardingRules, axes_tree):
    """Map a tree of logical-axis tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.spec(axes),
        axes_tree,
        is_leaf=lambda x: x is None or (isinstance(x, tuple)
                                        and all(a is None or isinstance(a, str)
                                                for a in x)),
    )


def tree_shardings(mesh: Mesh, rules: ShardingRules, axes_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_specs(rules, axes_tree),
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def fit_spec(mesh: Mesh, spec: PartitionSpec, shape) -> PartitionSpec:
    """Drop sharded axes that do not divide the dimension evenly (explicit
    pjit argument shardings require exact divisibility; GSPMD pads only
    internal constraints). Also truncates specs longer than the rank."""
    out = []
    seen = set()
    entries = tuple(spec)[: len(shape)]
    for d, ax in enumerate(entries):
        if ax is None:
            out.append(None)
            continue
        axes = tuple(a for a in ((ax,) if isinstance(ax, str) else tuple(ax))
                     if a not in seen)  # a mesh axis may appear only once
        if not axes:
            out.append(None)
            continue
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        if shape[d] % prod == 0:
            seen.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    return PartitionSpec(*out)


def fitted_shardings(mesh: Mesh, rules: ShardingRules, axes_tree, shapes_tree):
    """NamedShardings with non-divisible axes dropped per-leaf."""
    is_ax = lambda x: x is None or (isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x))

    def one(ax, leaf):
        spec = rules.spec(ax)
        return NamedSharding(mesh, fit_spec(mesh, spec, tuple(leaf.shape)))

    return jax.tree.map(one, axes_tree, shapes_tree, is_leaf=is_ax)


def active_mesh():
    """The mesh of the enclosing ``jax.set_mesh`` context, or None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _drop_manual(mesh, spec: PartitionSpec) -> PartitionSpec:
    """``spec`` without the mesh axes the context holds Manual: inside a
    shard_map body over some axes, only the others can be constrained."""
    manual = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
              if t == AxisType.Manual}
    if not manual:
        return spec
    out = []
    for ax in spec:
        axes = tuple(a for a in ((ax,) if isinstance(ax, str) else ax or ())
                     if a not in manual)
        out.append(None if not axes else axes[0] if len(axes) == 1 else axes)
    return PartitionSpec(*out)


def constrain(x, rules: Optional[ShardingRules], *logical_axes):
    """with_sharding_constraint by logical axes; a no-op without rules or
    outside a mesh context. Mesh axes that do not divide the dimension are
    dropped, as :func:`fit_spec` drops them from placements: a donated
    buffer only aliases strictly when the traced output sharding matches
    its fitted placement, and an evenly split head or batch dim keeps the
    per-device float program the same as on one device, where a padded
    split would change its shapes and so its summation order. A
    constraint the mesh rejects raises."""
    mesh = active_mesh()
    if mesh is None or rules is None:
        return x
    spec = fit_spec(mesh, _drop_manual(mesh, rules.spec(logical_axes)),
                    tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, spec)
