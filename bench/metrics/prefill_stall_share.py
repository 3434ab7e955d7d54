"""Share of the traced window in which the device ran prefill programs;
every live decode slot waits for them (the engine admits between decode
rounds), so this is the decode stall that prefill imposes."""


def read(ctx):
    if not ctx.trace["phase_calls"]["prefill"]:
        return None
    return 100.0 * ctx.trace["phase_s"]["prefill"] / ctx.trace["window_s"]
