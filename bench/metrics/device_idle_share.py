"""Share of the time inside the harness's engine-step spans in which no
operation ran on the device (mean over chips), in %."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.step_ns:
        return None
    return 100.0 * ctx.trace.idle_share()
