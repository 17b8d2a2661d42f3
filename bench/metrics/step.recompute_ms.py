"""step.recompute_ms: device self time per step, in ms, of what ``jax.remat``
computes again for the backward pass (op_name ``rematted_computation``),
averaged over the cell's chips. ``benchlib/scopes.py`` joins the trace's
operations to the step's scopes."""
from pathlib import Path

from benchlib import scopes


def read(ctx):
    return scopes.phase_ms(ctx, Path(__file__).resolve().parents[1], "recompute")
