"""Pallas-TPU paged decode attention over a latent KV pool (absorbed MLA).

DeepSeek-V2's multi-head latent attention caches one row per token per
layer: the normed ``r``-wide latent followed by the roped key part, ``D =
r + rope`` wide. With the key up-projection folded into the query
(``q_nope . W_uk^T``) decode is multi-query attention of every head over
those rows: each row is the key, and its first ``r`` columns the value.

  grid = (B,);  scalar-prefetch: block_tables (B, M), lengths (B,)
    program b walks slot b's live pages in chunks of ``pages`` pages
    (``pages * bs`` positions): one (H, D) x (D, pages*bs) score dot, an
    online-softmax update, one (H, pages*bs) x (pages*bs, r) value dot.
    Each chunk's pages are DMA'd from the pool in HBM straight into one
    contiguous VMEM buffer, double-buffered: the next chunk's copies (the
    next slot's first chunk, at a slot's last) start before this chunk's
    are waited on, so a row is read from HBM once and its transfer hides
    behind the previous chunk's compute.

Only live pages cost anything: the loop runs over the slot's live chunks
(positions ``[0, lengths[b]]``), and pages of its last chunk past the
slot's last live page are never copied. Their rows in the buffer hold
earlier pool rows (or the zeros the buffer starts as), always masked.

``pages`` trades grid overhead against wasted work in a slot's last
chunk. The GQA kernel (``paged_attention``) moves one 16-token block per
grid step and spends its time on per-step overhead, not bytes; here a
chunk of 16 pages (256 positions, 295 KB of rows at D = 576) is one DMA
batch and two matrix products.

Operands of both products are bf16 with f32 accumulation; the softmax
runs in f32. ``kernels.ref.latent_decode_ref`` is the pure-jnp oracle
with the same chunked op sequence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
PAGES_PER_STEP = 16


def _latent_kernel(tables_ref, lengths_ref, q_ref, pool_hbm, o_ref,
                   buf, sems, cur_scr, *, bs: int, pages: int, r: int,
                   scale: float):
    b = pl.program_id(0)
    B = pl.num_programs(0)
    chunk = pages * bs

    def last_page(slot):
        return lengths_ref[slot] // bs       # the new token's page

    def copy(slot, c, i, which):
        page = jnp.minimum(c * pages + i, last_page(slot))
        return pltpu.make_async_copy(
            pool_hbm.at[tables_ref[slot, page]],
            buf.at[which, pl.ds(i * bs, bs)], sems.at[which])

    def each_live(slot, c, which, fn):
        for i in range(pages):
            @pl.when(c * pages + i <= last_page(slot))
            def _():
                fn(copy(slot, c, i, which))

    @pl.when(b == 0)
    def _():
        # rows past a slot's last live page are masked, never NaN
        buf[...] = jnp.zeros_like(buf)
        cur_scr[0] = 0
        each_live(0, 0, 0, lambda cp: cp.start())

    cl = lengths_ref[b] + 1                  # positions [0, lengths[b]]
    n_chunks = (cl + chunk - 1) // chunk
    q = q_ref[0]                             # (H, D) bf16
    H = q.shape[0]

    def body(c, carry):
        m_run, l_run, acc = carry
        cur = cur_scr[0]
        nxt = 1 - cur

        @pl.when(c + 1 < n_chunks)
        def _():
            each_live(b, c + 1, nxt, lambda cp: cp.start())

        @pl.when(jnp.logical_and(c + 1 == n_chunks, b + 1 < B))
        def _():
            each_live(b + 1, 0, nxt, lambda cp: cp.start())

        each_live(b, c, cur, lambda cp: cp.wait())
        cur_scr[0] = nxt
        kv = buf[cur]                        # (chunk, D) bf16
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
        s = jnp.where(pos < cl, s, NEG_INF)
        m_new = jnp.maximum(m_run, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    a0 = jnp.zeros((H, r), jnp.float32)
    _, l_f, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l_f, 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r", "scale", "pages",
                                             "interpret"))
def latent_decode_attention(q, pool, block_tables, lengths, *, r: int,
                            scale: float, pages: int = PAGES_PER_STEP,
                            interpret: bool):
    """Paged absorbed-MLA decode. q: (B, H, D) queries in latent space
    (``q_nope . W_uk^T`` then the roped ``q_pe``); pool: (N_blocks, bs, D)
    latent rows (block 0 reserved null); block_tables: (B, M) int32;
    lengths: (B,) int32 — slot b attends positions ``[0, lengths[b]]``
    (its new row already written at ``lengths[b]``). Returns (B, H, r):
    the softmax-weighted sum of each row's first ``r`` columns, in q's
    dtype."""
    B, H, D = q.shape
    _, bs, _ = pool.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, t, ln: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, r), lambda b, t, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages * bs, D), pool.dtype),   # chunk buffers
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),                  # current buffer
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, pages=pages, r=r,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q.dtype),
        # a slot's last chunk prefetches the next slot's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), jnp.asarray(lengths, jnp.int32),
      q, pool)
