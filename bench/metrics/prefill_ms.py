"""Device time per prefill: the slot-prefill program and the write of
its KV into the pool, in ms per prefilled request."""


def read(ctx):
    if ctx.trace is None:
        return None
    ns, _ = ctx.trace.modules_matching(r"prefill_step|write_prefill_blocks")
    n = sum(len(s.prefills) for s in ctx.steps)
    return ns * 1e-6 / n if n and ns else None
