"""Pallas-TPU RG-LRU linear-recurrence scan (RecurrentGemma hot-spot).

Griffin's CUDA kernel streams the diagonal recurrence h_t = a_t h_{t-1} +
b_t through shared memory. The TPU adaptation tiles the channel dim into
VMEM lanes and runs the time loop INSIDE the kernel over a VMEM-resident
(S_blk, bd) block — channels are independent, so the grid parallelises
(batch, channel-block) while time stays sequential on the VPU:

  grid = (B, D / bd); per instance: fori over S with a (bd,) f32 carry.

The chunked time dimension keeps the working set (2 x S_blk x bd x 4B)
inside VMEM; the carry crosses grid steps through the h_last output block
(revisited per (b, d) instance — sequential minor-most S-chunk axis).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BD = 256       # channel block (lane multiple of 128)
DEFAULT_BS = 1024      # time chunk resident in VMEM


def _kernel(a_ref, b_ref, h0_ref, o_ref, hl_ref, *, ns: int, bs: int):
    s = pl.program_id(2)

    h = jnp.where(s == 0, h0_ref[0], hl_ref[0])     # (bd,) carry

    a = a_ref[0]                                    # (bs, bd)
    b = b_ref[0]

    def step(t, carry):
        h, out = carry
        h = a[t] * h + b[t]
        out = jax.lax.dynamic_update_index_in_dim(out, h, t, 0)
        return h, out

    out0 = jnp.zeros_like(a)
    h, out = jax.lax.fori_loop(0, bs, step, (h, out0))
    o_ref[0] = out
    hl_ref[0] = h


@functools.partial(jax.jit, static_argnames=("bd", "bs", "interpret"))
def rg_lru_scan(a, b, h0, *, bd: int = DEFAULT_BD, bs: int = DEFAULT_BS,
                interpret: bool):
    """a, b: (B, S, D) f32; h0: (B, D) f32 -> (h_all (B,S,D), h_last (B,D)).

    h_t = a_t * h_{t-1} + b_t per independent channel.
    """
    B, S, D = a.shape
    bd = min(bd, D)
    bs = min(bs, S)
    pd = (-D) % bd
    ps = (-S) % bs
    if pd or ps:
        pad3 = ((0, 0), (0, ps), (0, pd))
        # pad time with a=1, b=0: h_t = h_{t-1}, so the carry (h_last)
        # survives the padded steps unchanged
        a = jnp.pad(a, pad3, constant_values=1.0)
        b = jnp.pad(b, pad3)
        h0 = jnp.pad(h0, ((0, 0), (0, pd)))
    Sp, Dp = S + ps, D + pd

    out, h_last = pl.pallas_call(
        functools.partial(_kernel, ns=Sp // bs, bs=bs),
        grid=(B, Dp // bd, Sp // bs),
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, bd), lambda bi, di, si: (bi, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bd), lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, bd), lambda bi, di, si: (bi, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sp, Dp), a.dtype),
            jax.ShapeDtypeStruct((B, Dp), a.dtype),
        ],
        interpret=interpret,
    )(a, b, h0)
    return out[:, :S, :D], h_last[:, :D]
