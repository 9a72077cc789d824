"""Pallas-TPU expert-count histogram.

The Distribution-Only predictor's entire online input is the per-layer
expert histogram — a free side-effect of routing. On TPU a scatter-add
(`.at[].add`) lowers to a serialized scatter; this kernel instead reduces
one-hot comparisons per block on the VPU:

  grid = (N / bn,);  counts += sum_n (idx_blk[n] == iota_E)

The (bn, E) comparison matrix lives in VMEM/VREGs; accumulation revisits
the single (1, E) output block across the sequential grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 1024


def _kernel(idx_ref, o_ref, *, num_classes: int, valid: int, bn: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    idx = idx_ref[...]                              # (bn,)
    base = i * bn
    offs = base + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)[:, 0]
    classes = jax.lax.broadcasted_iota(jnp.int32, (bn, num_classes), 1)
    onehot = (idx[:, None] == classes) & (offs < valid)[:, None]
    o_ref[0] += onehot.astype(jnp.int32).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("num_classes", "bn", "interpret"))
def histogram(idx, num_classes: int, *, bn: int = DEFAULT_BN,
              interpret: bool):
    """idx: (N,) int32 in [0, num_classes) -> counts (num_classes,) int32."""
    N = idx.shape[0]
    bn = min(bn, max(N, 8))
    pn = (-N) % bn
    if pn:
        idx = jnp.pad(idx, (0, pn))
    out = pl.pallas_call(
        functools.partial(_kernel, num_classes=num_classes, valid=N, bn=bn),
        grid=((N + pn) // bn,),
        in_specs=[pl.BlockSpec((bn,), lambda i: (i,))],
        out_specs=pl.BlockSpec((1, num_classes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, num_classes), jnp.int32),
        interpret=interpret,
    )(idx)
    return out[0]


def histogram_offsets(idx, num_classes: int, *, bn: int = DEFAULT_BN,
                      interpret: bool):
    """Class histogram plus its exclusive prefix sum (slot start offsets).

    The sort-based dispatch packer consumes exactly this pair: counts give
    each slot's fill level, offsets give where each slot's contiguous run
    begins in the argsorted token order. Returns (counts, starts), both
    (num_classes,) int32.
    """
    counts = histogram(idx, num_classes, bn=bn, interpret=interpret)
    starts = jnp.cumsum(counts) - counts
    return counts, starts
