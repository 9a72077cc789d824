"""Pallas-TPU fused paged-decode attention (gather + flash softmax in one).

One decode step per grid slot walks the slot's ``block_tables`` row and
attends over its logical KV stream WITHOUT ever materialising the
``(B, M*bs, K, hd)`` gathered view the generic path builds in HBM:

  grid = (B, M);  scalar-prefetch: block_tables (B, M), lengths (B,)
    per (b, m): the in_spec index_map reads ``block_tables[b, m]`` and
    DMAs exactly that physical (bs, K, hd) KV block from the shared pool
    into VMEM — the gather IS the block fetch — then folds it into a
    flash-style running (max, sum, acc) online-softmax state held in
    VMEM scratch across the m-steps of slot b.

Masking happens in-kernel from logical-position arithmetic: positions
``>= lengths[b] + 1`` (ragged slots, and every null-block table entry —
unallocated entries point at reserved block 0 whose logical positions
are always past the valid length) and, for sliding-window archs,
positions ``< cache_len - window``. Blocks wholly outside the valid
window are skipped (``pl.when``), so decode compute scales with each
slot's VALID window, not the table's allocated width — the win the
generic gather path cannot have, since its HBM traffic is fixed at the
full ``(B, M*bs)`` view.

The pure-jnp oracle (``kernels.ref.paged_decode_ref``) runs the same
block-ordered accumulation over the materialised view, so fused vs
gather is bit-exact in fp32 — same dots, same exp/rescale sequence,
per logical block.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, bs: int, window: int,
                   scale: float):
    b, m = pl.program_id(0), pl.program_id(1)
    blocks = pl.num_programs(1)

    @pl.when(m == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cl = lengths_ref[b] + 1                       # new token sits at lengths
    start = m * bs
    # logical positions of this block's entries (2D iota: TPU constraint)
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)[0]
    mask = pos < cl
    live = start < cl
    if window > 0:
        mask &= pos >= cl - window
        live = jnp.logical_and(live, start + bs > cl - window)

    # skip blocks wholly outside the valid (windowed) range: unallocated
    # table entries (the null block) and positions behind the window never
    # cost compute — only the block DMA, which the index_map already
    # resolved to the one reserved null block
    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32)          # (K, G, hd)
        k = k_ref[0].astype(jnp.float32)          # (bs, K, hd)
        v = v_ref[0].astype(jnp.float32)
        # (K, G, hd) x (bs, K, hd) -> (K, G, bs): batch K, contract hd
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m_scr[...], s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_scr[...] - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        # (K, G, bs) x (bs, K, hd) -> (K, G, hd): batch K, contract bs
        acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)

    @pl.when(m == blocks - 1)
    def _():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-20)[..., None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           window: int = 0, interpret: bool):
    """Fused paged GQA decode. q: (B, K, G, hd) — query heads grouped by
    their KV head; k_pool/v_pool: (N_blocks, bs, K, hd) shared pool
    (block 0 reserved null); block_tables: (B, M) int32; lengths: (B,)
    int32 — the slot attends positions ``[0, lengths[b]]`` (its new token
    was already written at ``lengths[b]``), minus anything behind the
    sliding ``window``. Returns (B, K, G, hd) in q's dtype.
    """
    B, K, G, hd = q.shape
    _, bs, _, _ = k_pool.shape
    M = block_tables.shape[1]
    scale = 1.0 / math.sqrt(hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), lambda b, m, t, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, K, hd),
                         lambda b, m, t, ln: (t[b, m], 0, 0, 0)),
            pl.BlockSpec((1, bs, K, hd),
                         lambda b, m, t, ln: (t[b, m], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd),
                               lambda b, m, t, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G), jnp.float32),        # running max
            pltpu.VMEM((K, G), jnp.float32),        # running denominator
            pltpu.VMEM((K, G, hd), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q, k_pool, v_pool)
