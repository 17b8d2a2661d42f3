"""gossip.exposed_ms: the part of gossip.permute_ms, in ms per step, during
which no other operation runs on that chip, averaged over the chips whose
trace records operations in flight."""
from benchlib import trace as tr

PATTERN = "collective-permute"


def read(ctx):
    chips = tr.chips_in_flight(ctx.trace, ctx.devices, PATTERN)
    if not chips or not ctx.steps:
        return None
    exposed = [tr.exposed_s(ctx.trace, d, PATTERN) for d in chips]
    return 1e3 * sum(exposed) / len(exposed) / ctx.steps
