"""Device time per paged decode program execution, in ms."""


def read(ctx):
    if ctx.trace is None:
        return None
    ns, calls = ctx.trace.modules_matching(r"decode_step")
    return ns * 1e-6 / calls if calls and ns else None
