"""step.mfu: the whole training step's share, in %, of the chips' peak
bf16 FLOP/s: model FLOPs per token (``flops/<family>.py``) times the traced
run's tokens per second, over chips times the peak in ``peaks.json``."""


def read(ctx):
    if not ctx.tokens_per_s:
        return None
    return 100.0 * ctx.flops_per_token * ctx.tokens_per_s / (
        ctx.chips * ctx.peaks["bf16_flops"])
