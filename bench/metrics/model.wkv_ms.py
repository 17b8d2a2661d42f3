"""model.wkv_ms: device self time per step, in ms, of the RWKV-6 ``wkv``
scope (the chunked WKV recurrence: pairwise decays, intra-chunk scores,
bonus, readout of the carried state and its update, and the sequential
scan over chunks) in every phase (forward, backward, recompute), averaged
over the cell's chips.

``benchlib/scopes.py`` joins the trace's operations to the step's scopes;
an operation counts where ``wkv`` is one of its op_name's scopes.  Nothing
is read where the join fails, or where no operation carries the scope (a
program without it)."""
from pathlib import Path

from benchlib import scopes
from benchlib import trace as tr

SCOPE = "wkv"


def read(ctx):
    if scopes.split_ms(ctx, Path(__file__).resolve().parents[1]) is None:
        return None
    chips = [d for d in ctx.devices if ctx.trace.ops.get(d)]
    total = 0.0
    for d in chips:
        texts = {tr.short(text): text for _, _, text in tr.window_ops(ctx.trace, d)}
        for name, secs in tr.op_seconds(ctx.trace, d).items():
            found = ctx.op_scopes.get(name)
            rest = texts[name].split(" = ", 1)[-1]
            if (found is not None and found[1] == scopes.signature(rest)
                    and SCOPE in scopes._segments(found[0])):
                total += secs
    if not total:
        return None
    return 1e3 * total / (len(chips) * ctx.steps)
