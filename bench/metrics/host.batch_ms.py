"""host.batch_ms: mean wall time, in ms, of building one step's rows on the
host and handing them to the device (the harness's ``bench.batch`` span)
inside the traced window."""
from benchlib import trace as tr


def read(ctx):
    spans = tr.span_seconds(ctx.trace, "batch")
    return 1e3 * sum(spans) / len(spans) if spans else None
