"""Device time of one decode step: the decode programs' time in the
traced window over the engine's count of decode steps in it."""


def read(ctx):
    steps = ctx.counts["decode_steps"]
    if not steps or not ctx.trace["phase_calls"]["decode"]:
        return None
    return 1e3 * ctx.trace["phase_s"]["decode"] / steps
