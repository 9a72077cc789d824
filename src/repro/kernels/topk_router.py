"""Pallas-TPU fused top-k router: softmax + top-k + expert histogram.

One pass over the router logits produces everything the sort-based
dispatch pipeline needs, extending the accumulation pattern of
``kernels/histogram.py``:

  grid = (T / bn,);  per block (bn, E):
    probs = softmax(logits_blk)                      (VPU)
    for k in 0..K-1: gate/idx = max/argmax, mask     (K static, tiny)
    counts += sum_n onehot(idx)                      (revisited (1, E) block)

Compared with the unfused path (softmax -> ``lax.top_k`` -> scatter-add
histogram) the (bn, E) probability block never leaves VMEM between the
three stages, and the histogram — the Distribution-Only predictor's whole
online input — comes out as a free side effect of routing. Also emits the
per-row logsumexp so the router z-loss needs no second pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 256


def _kernel(logits_ref, idx_ref, gates_ref, probs_ref, lse_ref, counts_ref, *,
            num_experts: int, top_k: int, valid: int, bn: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = logits_ref[...].astype(jnp.float32)             # (bn, E)
    m = jnp.max(x, axis=-1, keepdims=True)
    ex = jnp.exp(x - m)
    den = jnp.sum(ex, axis=-1, keepdims=True)
    probs = ex / den
    probs_ref[...] = probs
    lse_ref[...] = m + jnp.log(den)

    offs = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    rowok = offs < valid                                # padded rows -> 0
    classes = jax.lax.broadcasted_iota(jnp.int32, (bn, num_experts), 1)
    work = probs
    acc = jnp.zeros((num_experts,), jnp.int32)
    sels, gs = [], []
    for _ in range(top_k):
        g = jnp.max(work, axis=-1)                      # (bn,)
        sel = jnp.argmax(work, axis=-1).astype(jnp.int32)
        hit = classes == sel[:, None]
        acc = acc + (hit & rowok).astype(jnp.int32).sum(axis=0)
        work = jnp.where(hit, -jnp.inf, work)
        sels.append(sel)
        gs.append(g)
    idx_ref[...] = jnp.stack(sels, axis=1)
    gates_ref[...] = jnp.stack(gs, axis=1)
    counts_ref[0] += acc


@functools.partial(jax.jit, static_argnames=("top_k", "bn", "interpret"))
def fused_topk_route(logits, top_k: int, *, bn: int = DEFAULT_BN,
                     interpret: bool):
    """logits: (T, E) -> (idx (T,K) i32, gates (T,K) f32 UN-normalised,
    probs (T,E) f32, lse (T,) f32, counts (E,) i32).

    Tie-breaking matches ``lax.top_k`` (lowest expert index wins), so the
    unfused reference router is bit-compatible on the assignments.
    """
    T, E = logits.shape
    bn = min(bn, max(T, 8))
    pn = (-T) % bn
    if pn:
        logits = jnp.pad(logits, ((0, pn), (0, 0)))
    idx, gates, probs, lse, counts = pl.pallas_call(
        functools.partial(_kernel, num_experts=E, top_k=top_k, valid=T, bn=bn),
        grid=((T + pn) // bn,),
        in_specs=[pl.BlockSpec((bn, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bn, top_k), lambda i: (i, 0)),
            pl.BlockSpec((bn, top_k), lambda i: (i, 0)),
            pl.BlockSpec((bn, E), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, E), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T + pn, top_k), jnp.int32),
            jax.ShapeDtypeStruct((T + pn, top_k), jnp.float32),
            jax.ShapeDtypeStruct((T + pn, E), jnp.float32),
            jax.ShapeDtypeStruct((T + pn, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, E), jnp.int32),
        ],
        interpret=interpret,
    )(logits)
    return idx[:T], gates[:T], probs[:T], lse[:T, 0], counts[0]
