"""The comparison that decides ``correct``.

Three numbers per run, each the worst over nodes (and steps or leaves):

  loss_gap    |L_program - L_reference| / |L_reference| over the compared
              steps
  grad_gap    per leaf, |‖g_program‖ - ‖g_reference‖| of the first gradient,
              over the larger of the reference's norm of that leaf and of
              the median leaf
  change_gap  the same for the parameters' change after the compared steps,
              over the leaves whose reference gradient is at least a
              thousandth of the median leaf's (below that a leaf moves by
              round-off alone)
"""
from __future__ import annotations

import numpy as np

MOVING_LEAF = 1e-3


def _norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    """prog, ref: (nodes, leaves); keep: (leaves,) bool."""
    p, r = prog[:, keep].astype(np.float64), ref[:, keep].astype(np.float64)
    med = np.median(r, axis=1, keepdims=True)
    return float(np.max(np.abs(p - r) / np.maximum(r, med)))


def gaps(prog: dict, ref: dict) -> dict:
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    moving = np.all(g_ref >= MOVING_LEAF * np.median(g_ref, axis=1, keepdims=True),
                    axis=0)
    every = np.ones(g_ref.shape[1], bool)
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": _norm_gap(np.asarray(prog["grad_norms"]), g_ref, every),
        "change_gap": _norm_gap(np.asarray(prog["change_norms"]),
                                np.asarray(ref["change_norms"]), moving),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(every number that has a limit is within it, {name: {"value",
    "limit"}} of those numbers).  A number that is not finite fails."""
    checks = {k: {"value": values[k], "limit": v} for k, v in limits.items()}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
