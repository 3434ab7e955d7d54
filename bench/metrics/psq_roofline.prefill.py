"""PSQ kernels' share of their roofline during prefill.

The least time the chip could take for the prefill calls' PSQ work,
counted from logical shapes (bench/work.py: every projection of every
layer, once per prefill call, at the true prompt tokens per call), over
the device time of the ``psq_matmul`` kernels inside the prefill
programs."""
from bench import work


def read(ctx):
    calls, tokens = ctx.counts["prefill_calls"], ctx.counts["prefill_tokens"]
    kernel_s = ctx.trace["psq_s"]["prefill"]
    if not calls or not tokens or kernel_s <= 0:
        return None
    least = work.psq_min_seconds(calls * ctx.cfg["num_hidden_layers"],
                                 tokens / calls, ctx.layers, ctx.psq,
                                 ctx.peak)
    return 100.0 * least / kernel_s
