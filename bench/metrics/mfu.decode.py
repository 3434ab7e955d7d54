"""The decode step's share of the chip's bf16 peak: dense-equivalent
model FLOPs of the tokens decoded in the traced window (2 x parameters
x tokens, attention over each token's context added) over the decode
programs' device time."""
from bench import work


def read(ctx):
    tokens = ctx.counts["decode_tokens"]
    dev_s = ctx.trace["phase_s"]["decode"]
    if not tokens or dev_s <= 0:
        return None
    flops = work.model_flops(ctx.cfg, ctx.layers, tokens,
                             ctx.counts["decode_context"])
    return 100.0 * flops / (dev_s * ctx.peak["bf16_flops_per_s"])
