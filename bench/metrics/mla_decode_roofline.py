"""Share of the HBM roofline reached by the paged latent-decode kernel
(absorbed MLA, ``repro.kernels.latent_attention``): the bytes of the live
latent rows each call must read (every decoding sequence's live blocks,
from the harness's record of its context; the architecture's
``kv_read_bytes``) over the kernel's device time at peak HBM bandwidth,
per chip."""

# the kernel's device ops, as the profiler names them
LATENT_DECODE = r"latent_decode|latent_kernel"


def read(ctx):
    if ctx.trace is None:
        return None
    ns = ctx.trace.ops_matching(LATENT_DECODE)
    need = sum(ctx.arch.kv_read_bytes(ctx.D, c, ctx.block_size)
               for s in ctx.steps for c in s.decodes)
    if not ns or not need:
        return None
    return 100.0 * need / ctx.chips / (ns * 1e-9 * ctx.peak["hbm_bytes_per_s"])
