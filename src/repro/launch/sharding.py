"""Logical partition specs → mesh shardings.

Model modules declare per-weight logical specs (entries: None | "model",
see ``models/common.ParamDef``).  This module materializes them for a
concrete mesh and gossip placement:

  * gossip placement (G > 1): every leaf gains a leading stacked-replica dim
    sharded over the gossip axes: P(gossip_axes, *logical).
  * degenerate placement (G == 1, e.g. kimi-k2 on one pod): no stacking;
    instead remaining non-model axes FSDP-shard the largest divisible
    unsharded dim of each leaf.

Divisibility is always validated against the mesh — a spec that does not
divide falls back to replication on that dim (never a compile error).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

PyTree = Any

__all__ = [
    "leaf_sharding",
    "param_shardings",
    "stack_abstract",
    "batch_sharding",
    "tree_size_bytes",
]


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return math.prod(mesh.shape[a] for a in axis)
    return mesh.shape[axis]


def _validated_spec(shape, spec_entries, mesh) -> list:
    out = []
    for dim, ax in zip(shape, spec_entries):
        if ax is not None and dim % _axis_size(mesh, ax) == 0:
            out.append(ax)
        else:
            out.append(None)
    return out


def leaf_sharding(
    shape: tuple[int, ...],
    logical: tuple[Optional[str], ...],
    mesh: jax.sharding.Mesh,
    gossip_axes: tuple[str, ...],
    *,
    stacked: bool,
    fsdp: bool = False,
) -> NamedSharding:
    """Sharding for one (possibly gossip-stacked) weight leaf."""
    if stacked:
        entries = _validated_spec(shape[1:], logical, mesh)
        return NamedSharding(mesh, P(gossip_axes, *entries))
    entries = _validated_spec(shape, logical, mesh)
    if fsdp and any(e not in (None, "model") for e in entries):
        fsdp = False  # leaf already uses a data/pod axis explicitly
    if fsdp:
        fsdp_axes = tuple(a for a in mesh.axis_names if a != "model")
        for cand in (fsdp_axes, fsdp_axes[-1:] if fsdp_axes else ()):
            size = _axis_size(mesh, cand) if cand else 1
            if not cand:
                continue
            # shard the largest still-unsharded divisible dim
            dims = sorted(
                (d for d in range(len(shape)) if entries[d] is None),
                key=lambda d: -shape[d],
            )
            for d in dims:
                if shape[d] % size == 0:
                    entries[d] = cand
                    break
            else:
                continue
            break
    return NamedSharding(mesh, P(*entries))


def param_shardings(
    abstract: PyTree,
    logical_specs: PyTree,
    mesh: jax.sharding.Mesh,
    gossip_axes: tuple[str, ...],
    *,
    stacked: bool,
    fsdp: bool = False,
) -> PyTree:
    """Shardings for a whole (possibly stacked) abstract param tree.

    ``logical_specs`` mirrors the *unstacked* tree; when ``stacked`` the
    abstract leaves carry the extra leading G dim.
    """
    return jax.tree.map(
        lambda leaf, spec: leaf_sharding(
            leaf.shape, spec, mesh, gossip_axes, stacked=stacked, fsdp=fsdp
        ),
        abstract,
        logical_specs,
    )


def stack_abstract(abstract: PyTree, g: int) -> PyTree:
    """Prepend the gossip-replica dim to an abstract tree."""
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((g,) + tuple(l.shape), l.dtype), abstract
    )


def batch_sharding(
    mesh: jax.sharding.Mesh,
    gossip_axes: tuple[str, ...],
    ndim: int,
    *,
    stacked: bool,
) -> NamedSharding:
    """Training batches (G, b, ...): G over the gossip axes (stacked), or,
    when G == 1, b over all non-model axes."""
    if stacked:
        return NamedSharding(mesh, P(gossip_axes, *([None] * (ndim - 1))))
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    return NamedSharding(
        mesh, P(None, data_axes if data_axes else None, *([None] * (ndim - 2)))
    )


def tree_size_bytes(tree: PyTree) -> int:
    return sum(
        int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tree)
    )
