"""Mesh construction + NamedSharding resolution from logical-axis rules.

The declarative per-leaf rules live in :mod:`repro.configs.base`
(``LM_LOGICAL_RULES`` et al.); this module resolves them against a concrete
mesh into ``PartitionSpec`` / ``NamedSharding`` trees, guarding every
placement for divisibility so one rule set serves the 512-chip production
meshes and the 8-fake-device host tests alike.  It also provides the
``shard_map``-based data-parallel wrapper used by batch-sharded pipelines.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# The rule tables are DECLARED in repro.configs.base but must be loaded
# lazily: model modules import repro.dist, and repro.configs imports the
# model modules — an eager import here would re-enter a partially
# initialized repro.models.* depending on which side is imported first.
_RULE_EXPORTS = {
    "LM_RULES": "LM_LOGICAL_RULES",
    "GNN_RULES": "GNN_LOGICAL_RULES",
    "RECSYS_RULES": "RECSYS_LOGICAL_RULES",
    "LOGICAL_TO_MESH": "LOGICAL_TO_MESH",
    "MOE_FFN_LOGICAL_RULES": "MOE_FFN_LOGICAL_RULES",
}


def __getattr__(name):  # PEP 562: resolve rule tables on first access
    if name in _RULE_EXPORTS:
        from repro.configs import base as _config_base
        return getattr(_config_base, _RULE_EXPORTS[name])
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ----------------------------------------------------------------- mesh utils


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes that carry the batch (data-parallel) dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_spec(mesh: Mesh, rank: int) -> P:
    """P sharding dim 0 over the data axes, replicating the rest."""
    ax = batch_axes(mesh)
    lead = ax if len(ax) > 1 else (ax[0] if ax else None)
    return P(lead, *([None] * (rank - 1)))


def _entry_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(a for a in entry if a is not None)


def guard_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Drop per-dim placements that are absent from the mesh, already used on
    an earlier dim, or do not divide the dim — GSPMD-safe by construction."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used: set = set()
    out = []
    for entry, dim in zip(entries, shape):
        axes = tuple(a for a in _entry_axes(entry)
                     if a in mesh.axis_names and a not in used)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if not axes or size <= 1 or dim % size != 0:
            out.append(None)
        else:
            used.update(axes)
            out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


def input_sharding(mesh: Mesh, shape: Sequence[int], spec: P) -> NamedSharding:
    """NamedSharding for an input of the given shape, divisibility-guarded."""
    return NamedSharding(mesh, guard_spec(spec, shape, mesh))


# ------------------------------------------------------------ rule resolution


def _leaf_name(path) -> Optional[str]:
    """Last string key on a tree path (skipping list/tuple indices)."""
    for entry in reversed(path):
        name = getattr(entry, "key", None)
        if isinstance(name, str):
            return name
        name = getattr(entry, "name", None)
        if isinstance(name, str):
            return name
    return None


def _logical_to_entry(logical: Optional[str],
                      fsdp_axes: Tuple[str, ...]) -> Optional[Tuple[str, ...]]:
    from repro.configs.base import LOGICAL_TO_MESH
    mapped = LOGICAL_TO_MESH.get(logical)
    if mapped == "__fsdp__":
        mapped = tuple(fsdp_axes)
    return mapped


def spec_for_leaf(name: Optional[str], shape: Sequence[int], mesh: Mesh,
                  rules: Dict[str, tuple],
                  fsdp_axes: Tuple[str, ...] = ("data",),
                  is_moe: bool = False) -> P:
    """Resolve one leaf's logical rule to a guarded PartitionSpec."""
    from repro.configs.base import MOE_FFN_LOGICAL_RULES
    rule = None
    if name is not None:
        if is_moe and name in MOE_FFN_LOGICAL_RULES and \
                len(shape) >= len(MOE_FFN_LOGICAL_RULES[name]):
            rule = MOE_FFN_LOGICAL_RULES[name]
        else:
            rule = rules.get(name)
    if rule is None:
        return P()
    # rules address the TRAILING dims; leading (layer-stack/expert) dims
    # replicate unless the rule names them explicitly.  A leaf with FEWER
    # dims than its rule (a squeezed/bias variant sharing the name) keeps
    # only the rule's trailing entries, preserving the alignment contract.
    rule = rule[-len(shape):] if shape else ()
    lead = [None] * (len(shape) - len(rule))
    entries = lead + [_logical_to_entry(l, tuple(fsdp_axes)) for l in rule]
    return guard_spec(P(*entries), shape, mesh)


def tree_specs(tree: Any, rules: Dict[str, tuple], mesh: Mesh, *,
               fsdp_axes: Tuple[str, ...] = ("data",),
               is_moe: bool = False) -> Any:
    """PartitionSpec tree for a parameter tree under the given logical rules."""
    def one(path, leaf):
        shape = getattr(leaf, "shape", ())
        return spec_for_leaf(_leaf_name(path), shape, mesh, rules,
                             fsdp_axes=fsdp_axes, is_moe=is_moe)
    return jax.tree_util.tree_map_with_path(one, tree)


def tree_shardings(tree: Any, rules: Dict[str, tuple], mesh: Mesh, *,
                   fsdp_axes: Tuple[str, ...] = ("data",),
                   is_moe: bool = False) -> Any:
    """NamedSharding tree (device-placeable form of ``tree_specs``)."""
    specs = tree_specs(tree, rules, mesh, fsdp_axes=fsdp_axes, is_moe=is_moe)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


# -------------------------------------------------------- data-parallel wrap


def data_parallel(fn, mesh: Mesh):
    """``shard_map`` wrapper splitting every arg/output's leading dim over the
    mesh's batch axes (all axes if the mesh has no data axis).

    ``fn`` must be shardwise-independent: no cross-batch reductions, each
    output carries the global batch on dim 0.
    """
    ax = batch_axes(mesh) or tuple(mesh.axis_names)
    spec = P(ax if len(ax) > 1 else ax[0])
    # keyed on (treedef, leaf avals): grows like a jit cache, one entry per
    # distinct input structure/shape set
    cache: Dict[Any, Any] = {}

    def wrapped(*args):
        key = (jax.tree.structure(args),
               tuple((l.shape, str(l.dtype))
                     for l in jax.tree.leaves(args)))
        sm = cache.get(key)
        if sm is None:
            out_sds = jax.eval_shape(fn, *args)
            in_specs = jax.tree.map(lambda _: spec, args)
            out_specs = jax.tree.map(lambda _: spec, out_sds)
            sm = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                       out_specs=out_specs, check_vma=False))
            cache[key] = sm
        return sm(*args)

    return wrapped
