"""Share of the HBM roofline reached by the whole decode program: the
bytes a decode step must read (weights of the experts its routing hit,
the attention, router and norm weights of the held layers, the LM head,
the live KV) over its device time at peak HBM bandwidth, per chip.
Experts hit come from decode-only steps' expert counts; a step that also
prefilled is charged the mean of those."""

import numpy as np

import counts


def read(ctx):
    if ctx.trace is None:
        return None
    D = ctx.D
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
        need += (h * counts.expert_bytes(D)
                 + D["L"] * counts.layer_dense_bytes(D) + counts.head_bytes(D)
                 + sum(counts.kv_read_bytes(D, c, ctx.block_size)
                       for c in s.decodes))
    ns, _ = ctx.trace.modules_matching(r"decode_step")
    if not ns:
        return None
    return 100.0 * need / ctx.chips / (ns * 1e-9 * ctx.peak["hbm_bytes_per_s"])
