"""gossip.permute_ms: device time per step, in ms, of the collective-permute
operations that carry the gossip round's neighbour exchange: on each chip
the union of their intervals, in flight or waited on, averaged over chips.

The profiler records operations in flight (the ``Async XLA Ops`` line) on
the first chip only, so only chips whose trace has them count.
"""
from benchlib import trace as tr

PATTERN = "collective-permute"


def read(ctx):
    chips = tr.chips_in_flight(ctx.trace, ctx.devices, PATTERN)
    if not chips or not ctx.steps:
        return None
    per_chip = [tr.length(tr.union(tr.matching(ctx.trace, d, PATTERN))) for d in chips]
    return 1e3 * sum(per_chip) / len(per_chip) / ctx.steps
