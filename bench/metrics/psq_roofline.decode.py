"""PSQ kernels' share of their roofline during decode.

The least time the chip could take for the decode steps' PSQ work,
counted from logical shapes (bench/work.py: every projection of every
layer, once per step, at the tokens emitted per step), over the device
time of the ``psq_matmul`` kernels inside the decode programs."""
from bench import work


def read(ctx):
    steps, tokens = ctx.counts["decode_steps"], ctx.counts["decode_tokens"]
    kernel_s = ctx.trace["psq_s"]["decode"]
    if not steps or not tokens or kernel_s <= 0:
        return None
    least = work.psq_min_seconds(steps * ctx.cfg["num_hidden_layers"],
                                 tokens / steps, ctx.layers, ctx.psq,
                                 ctx.peak)
    return 100.0 * least / kernel_s
