"""Pallas-TPU grouped expert FFN GEMM (the MoE compute hot-spot).

The GPU systems the paper builds on use CUDA "grouped GEMM" kernels for
the per-expert FFN. The TPU adaptation tiles the three expert matmuls into
MXU-aligned VMEM blocks and fuses gate/up/activation/down into one kernel,
so the (tokens_per_slot, d_ff) intermediate never round-trips HBM:

  grid = (slots, T/bt, F/bf)      (sequential minor-most f over d_ff)
  per step:  h = act(x_blk @ wg_blk) [* (x_blk @ wu_blk)]   (bt, bf)
             acc += h @ wd_blk                              (bt, d) f32

Block shapes are multiples of (8, 128) so both matmuls keep the MXU fed;
the f-loop accumulates into a VMEM f32 scratch, written once at f == F-1.
Validated against ref.moe_gemm_ref in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 128       # token-block (second-minor >= 8)
DEFAULT_BF = 512       # d_ff block (lane multiple of 128)
VMEM_HEADROOM = 4 << 20   # Mosaic internal scratch on top of the blocks


def _kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, *,
            activation: str, nf: int):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # (bt, d)
    wu = wu_ref[0]                                 # (d, bf)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    if activation == "swiglu":
        wg = wg_ref[0]
        g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * u
    elif activation == "gelu":
        h = jax.nn.gelu(u)
    else:
        h = jnp.maximum(u, 0.0)
    wd = wd_ref[0]                                 # (bf, d)
    acc_ref[...] += jnp.dot(h.astype(x.dtype), wd,
                            preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("activation", "bt", "bf", "interpret"))
def moe_gemm(x, w_gate, w_up, w_down, *, activation: str = "swiglu",
             bt: int = DEFAULT_BT, bf: int = DEFAULT_BF,
             interpret: bool):
    """x: (S, T, d); w_gate/w_up: (S, d, F); w_down: (S, F, d) -> (S, T, d).

    T and F are padded to block multiples internally (zero padding is
    exact for all supported activations: act(0)=0 rows contribute 0).
    """
    S, T, d = x.shape
    F = w_up.shape[-1]
    bt = min(bt, max(8, T))
    bf = min(bf, F)
    pt = (-T) % bt
    pf = (-F) % bf
    if pt:
        x = jnp.pad(x, ((0, 0), (0, pt), (0, 0)))
    if pf:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, pf)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pf)))
        w_down = jnp.pad(w_down, ((0, 0), (0, pf), (0, 0)))
    Tp, Fp = T + pt, F + pf
    nf = Fp // bf
    # double-buffered x/wg/wu/wd/out blocks + the f32 accumulator: at
    # Mixtral widths (d 4096, bf 512) that is ~30 MB, past the 16 MB
    # default scoped-VMEM limit, so the kernel asks for what it uses
    isz, wsz = x.dtype.itemsize, w_up.dtype.itemsize
    vmem = 2 * (2 * bt * d * isz + 3 * d * bf * wsz) + bt * d * 4

    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation, nf=nf),
        grid=(S, Tp // bt, nf),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda s, t, f: (s, t, 0)),
            pl.BlockSpec((1, d, bf), lambda s, t, f: (s, 0, f)),
            pl.BlockSpec((1, d, bf), lambda s, t, f: (s, 0, f)),
            pl.BlockSpec((1, bf, d), lambda s, t, f: (s, f, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, d), lambda s, t, f: (s, t, 0)),
        out_shape=jax.ShapeDtypeStruct((S, Tp, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + VMEM_HEADROOM),
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
    return out[:, :T]
