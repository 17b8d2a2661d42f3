"""kernel.gossip_update.roofline: the fused gossip-update kernel's share, in
%, of its HBM roofline: the least time its bytes need at the chip's peak
bandwidth over the summed device time of its events, averaged over chips.

The kernel is bandwidth-bound (a few FLOPs per element), so bytes bound it.
Per leaf of P elements at degree ``deg`` one pass reads theta, the deg
neighbour buffers and the gradient in the parameter dtype and the float32
momentum, and writes theta and the momentum back.
"""
from benchlib import trace as tr

PATTERN = "_leaf_update"   # the jitted wrapper that names the kernel's custom call
MOMENTUM_BYTES = 4


def bytes_per_step(leaf_sizes, itemsize: int, deg: int) -> int:
    per_element = itemsize * (1 + deg + 1) + MOMENTUM_BYTES \
        + itemsize + MOMENTUM_BYTES
    return sum(leaf_sizes) * per_element


def read(ctx):
    per_device = [sum(e - s for s, e, _ in tr.matching(ctx.trace, d, PATTERN))
                  for d in ctx.devices]
    if not any(per_device) or not ctx.steps or not ctx.degree:
        return None
    kernel_s = sum(per_device) / len(per_device)
    least_s = (bytes_per_step(ctx.leaf_sizes, ctx.param_itemsize, ctx.degree)
               * ctx.steps / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
