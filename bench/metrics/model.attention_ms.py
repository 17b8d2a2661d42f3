"""model.attention_ms: device self time per step, in ms, of the model's
``attention`` scope in every phase (forward, backward, recompute): QKV,
RoPE, scores, mask, softmax, weighted sum and output projection, averaged
over the cell's chips. ``benchlib/scopes.py`` joins the trace's operations
to the step's scopes."""
from pathlib import Path

from benchlib import scopes


def read(ctx):
    return scopes.part_ms(ctx, Path(__file__).resolve().parents[1], "attention")
