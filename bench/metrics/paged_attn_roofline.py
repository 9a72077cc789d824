"""Share of the HBM roofline reached by the fused paged-decode attention
kernel: the bytes of the live KV blocks each call must read (from the
harness's record of every decoding sequence's context) over the kernel's
device time at peak HBM bandwidth (the architecture's ``kv_read_bytes``).
Per chip: a chip holds its share of the KV heads."""

import kernels


def read(ctx):
    if ctx.trace is None:
        return None
    ns = ctx.trace.ops_matching(kernels.PAGED_ATTENTION)
    need = sum(ctx.arch.kv_read_bytes(ctx.D, c, ctx.block_size)
               for s in ctx.steps for c in s.decodes)
    if not ns or not need:
        return None
    return 100.0 * need / ctx.chips / (ns * 1e-9 * ctx.peak["hbm_bytes_per_s"])
