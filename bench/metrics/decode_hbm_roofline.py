"""Share of the HBM roofline reached by the whole decode program: the
bytes a decode step must read (weights of the experts its routing hit,
what it reads whatever the routing, the live KV: the architecture's
``expert_bytes``, ``fixed_bytes`` and ``kv_read_bytes``) over its device
time at peak HBM bandwidth, per chip. Experts hit come from decode-only
steps' expert counts; a step that also prefilled is charged the mean of
those."""

import numpy as np


def read(ctx):
    if ctx.trace is None:
        return None
    A, D = ctx.arch, ctx.D
    steps = [s for s in ctx.steps if s.decodes]
    hits = [int((s.counts > 0).sum()) for s in ctx.steps
            if s.decodes and not s.prefills and s.counts is not None]
    if not steps or not hits:
        return None
    mean_hits = float(np.mean(hits))
    need = 0.0
    for s in steps:
        h = (int((s.counts > 0).sum()) if not s.prefills
             and s.counts is not None else mean_hits)
        need += (h * A.expert_bytes(D) + A.fixed_bytes(D)
                 + sum(A.kv_read_bytes(D, c, ctx.block_size)
                       for c in s.decodes))
    ns, _ = ctx.trace.modules_matching(r"decode_step")
    if not ns:
        return None
    return 100.0 * need / ctx.chips / (ns * 1e-9 * ctx.peak["hbm_bytes_per_s"])
