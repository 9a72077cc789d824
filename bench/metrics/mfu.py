"""Model FLOP utilisation of the whole serving step: the top-k model
FLOPs of the traced window (each prompt's real tokens, each decoded
token, attention at its actual context; no bucket or capacity padding;
the architecture's ``prefill_flops`` and ``decode_flops``) over window
seconds x chips x peak bf16 FLOP/s."""


def read(ctx):
    steps = ctx.steps
    if not steps:
        return None
    A, D = ctx.arch, ctx.D
    flops = sum(A.prefill_flops(D, p) for s in steps for p in s.prefills)
    flops += sum(A.decode_flops(D, c) for s in steps for c in s.decodes)
    window = steps[-1].t1 - steps[0].t0
    if not flops or window <= 0:
        return None
    return 100.0 * flops / (window * ctx.chips * ctx.peak["bf16_flops_per_s"])
