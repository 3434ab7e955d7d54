"""Device time of prefill per 1000 prompt tokens prefilled in the
traced window (true tokens, padding not counted)."""


def read(ctx):
    tokens = ctx.counts["prefill_tokens"]
    if not tokens or not ctx.trace["phase_calls"]["prefill"]:
        return None
    return 1e3 * ctx.trace["phase_s"]["prefill"] / (tokens / 1e3)
