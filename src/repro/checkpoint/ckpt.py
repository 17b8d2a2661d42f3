"""Checkpointing: flat-keyed npz snapshots of arbitrary pytrees.

Stores (params, opt_state, step, rng) with tree structure recovered from the
flattened key paths.  Host-side (fully addressable) arrays; for the
production mesh, the launcher gathers per-node shards before saving (the
decentralized state is the *stacked* (G, ...) tree, so one file captures
every replica).
"""
from __future__ import annotations

import json
import os
from typing import Any

import jax
import numpy as np

PyTree = Any

__all__ = [
    "save_checkpoint", "load_checkpoint", "load_checkpoint_extra",
    "latest_step", "validate_run_config",
]

_SEP = "/"
# sidecar npz key for the JSON "extra" payload (engine run state beyond the
# array tree: controller phase/rung/logs, membership tracking) — chosen so
# it can never collide with a flattened tree path (those never start with _)
_EXTRA_KEY = "__extra__"


def _flatten(tree: PyTree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_part(p) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _part(p) -> str:
    if isinstance(p, jax.tree_util.DictKey):
        return str(p.key)
    if isinstance(p, jax.tree_util.SequenceKey):
        return f"#{p.idx}"
    if isinstance(p, jax.tree_util.GetAttrKey):
        return f"@{p.name}"
    return str(p)


def save_checkpoint(
    directory: str, step: int, state: PyTree, *, keep: int = 3,
    extra: dict | None = None,
) -> str:
    """Write ``<dir>/step_<n>.npz`` (+ manifest); prune to ``keep`` newest.

    ``extra``: optional JSON-serializable dict rides in the same npz (one
    atomic artifact) under a reserved key — crash-consistent resume needs
    the engine run state (``snapshot_extra``) saved with the arrays it
    belongs to, never in a second file that could be torn from them.
    """
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"step_{step:010d}.npz")
    flat = _flatten(state)
    if _EXTRA_KEY in flat:
        raise ValueError(f"state tree uses the reserved key {_EXTRA_KEY!r}")
    if extra is not None:
        flat[_EXTRA_KEY] = np.asarray(json.dumps(extra))
    np.savez(path, **flat)
    with open(os.path.join(directory, "manifest.json"), "w") as f:
        json.dump({"latest_step": step}, f)
    ckpts = sorted(p for p in os.listdir(directory) if p.startswith("step_"))
    for old in ckpts[:-keep]:
        os.remove(os.path.join(directory, old))
    return path


def latest_step(directory: str) -> int | None:
    mf = os.path.join(directory, "manifest.json")
    if not os.path.exists(mf):
        return None
    with open(mf) as f:
        return json.load(f)["latest_step"]


def load_checkpoint(directory: str, template: PyTree, step: int | None = None) -> tuple[PyTree, int]:
    """Restore into the structure of ``template`` (shapes validated)."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint manifest in {directory}")
    path = os.path.join(directory, f"step_{step:010d}.npz")
    data = np.load(path)
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for p, leaf in paths:
        key = _SEP.join(_part(x) for x in p)
        arr = data[key]
        if tuple(arr.shape) != tuple(np.shape(leaf)):
            raise ValueError(
                f"checkpoint leaf {key}: shape {arr.shape} != template {np.shape(leaf)}"
            )
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves), step


def validate_run_config(
    recorded: dict, *, topology: str, bucket_mb: float | None = None,
    n: int | None = None, n_label: str = "node count",
) -> None:
    """Fail-fast resume: compare a checkpoint's recorded ``run_config``
    against the resuming run's configuration.

    A mismatched resume (different topology, bucket layout, or — for the
    fixed-mesh trainer — gossip size) would otherwise surface as an opaque
    leaf-shape or tree-structure error mid-restore, or worse, silently
    change the mixing semantics.  Raises a ``ValueError`` naming BOTH the
    checkpointed and the configured value.  Checkpoints written before
    ``run_config`` existed (empty dict) skip the check.
    """
    if not recorded:
        return
    ck_topo = recorded.get("topology")
    if ck_topo is not None and str(ck_topo) != str(topology):
        raise ValueError(
            f"resume config mismatch: checkpoint was written with topology "
            f"{ck_topo!r} but this run is configured with {topology!r}"
        )
    if "bucket_mb" in recorded:
        ck_mb = recorded["bucket_mb"]
        ours = None if bucket_mb is None else float(bucket_mb)
        if (ck_mb is None) != (ours is None) or (
            ck_mb is not None and float(ck_mb) != ours
        ):
            raise ValueError(
                f"resume config mismatch: checkpoint was written with "
                f"bucket_mb={ck_mb} but this run is configured with "
                f"bucket_mb={ours}"
            )
    ck_n = recorded.get("n")
    if n is not None and ck_n is not None and int(ck_n) != int(n):
        raise ValueError(
            f"resume config mismatch: checkpoint was written with "
            f"{n_label} {int(ck_n)} but this run is configured with {int(n)}"
        )


def load_checkpoint_extra(directory: str, step: int | None = None) -> dict | None:
    """The ``extra`` payload saved with a checkpoint (None if it has none)."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint manifest in {directory}")
    data = np.load(os.path.join(directory, f"step_{step:010d}.npz"))
    if _EXTRA_KEY not in data:
        return None
    return json.loads(str(data[_EXTRA_KEY]))
