"""Production meshes and gossip-axis placement.

single-pod : (16, 16)    ("data", "model")           — 256 chips (one v5e pod)
multi-pod  : (2, 16, 16) ("pod", "data", "model")    — 512 chips (2 pods)

The *gossip axes* enumerate decentralized nodes; the remaining axes shard
each node's replica (TP/EP over "model"; FSDP over "data" for the pod-level
placement).  Everything is a function — importing this module never touches
jax device state.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax

__all__ = [
    "make_production_mesh",
    "make_mesh",
    "gossip_axes_for",
    "gossip_size",
]


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    return jax.make_mesh(
        tuple(shape),
        tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def gossip_axes_for(arch_name: str, mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Which mesh axes enumerate gossip nodes for an architecture.

    Default: every non-"model" axis is a gossip axis (node = one TP group).
    1T-scale MoE (kimi-k2): a replica needs the whole pod (FSDP x EP), so
    gossip runs across pods only — () on a single pod (degenerate G=1,
    decentralization scale-inapplicable; DESIGN.md §4), ("pod",) multi-pod.
    """
    names = tuple(mesh.axis_names)
    if arch_name.startswith("kimi-k2"):
        return ("pod",) if "pod" in names else ()
    return tuple(a for a in names if a != "model")


def gossip_size(mesh: jax.sharding.Mesh, gossip_axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in gossip_axes) if gossip_axes else 1
