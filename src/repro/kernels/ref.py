"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_gemm_ref(x, w_gate, w_up, w_down, activation: str = "swiglu"):
    """Grouped expert FFN.
    x: (S, T, d); w_gate/w_up: (S, d, F); w_down: (S, F, d) -> (S, T, d)."""
    if activation == "swiglu":
        g = jnp.einsum("std,sdf->stf", x, w_gate.astype(x.dtype))
        u = jnp.einsum("std,sdf->stf", x, w_up.astype(x.dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = jnp.einsum("std,sdf->stf", x, w_up.astype(x.dtype))
        h = (jax.nn.gelu(h.astype(jnp.float32)) if activation == "gelu"
             else jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
    return jnp.einsum("stf,sfd->std", h, w_down.astype(x.dtype))


def histogram_ref(idx, num_classes: int):
    """idx: (N,) int32 -> counts (num_classes,) int32."""
    return jnp.zeros((num_classes,), jnp.int32).at[idx].add(
        jnp.ones_like(idx))


def paged_decode_ref(q, k_view, v_view, lengths, *, window: int = 0,
                     block_size: int = 16):
    """Gather-path paged-decode oracle for ``kernels.paged_attention``.

    Runs over the MATERIALISED logical view (its defining cost) but with
    the kernel's exact blockwise online-softmax op sequence — same dots,
    same exp/rescale order, same block-skip — so fused vs gather is
    bit-exact in fp32, not merely allclose.

    q: (B, K, G, hd); k_view/v_view: (B, M*bs, K, hd) gathered views;
    lengths: (B,) int32 (new token already written at ``lengths[b]``).
    Returns (B, K, G, hd) in q's dtype.
    """
    B, K, G, hd = q.shape
    bs = block_size
    M = k_view.shape[1] // bs
    scale = 1.0 / (hd ** 0.5)
    neg_inf = -1e30
    cl = jnp.asarray(lengths, jnp.int32) + 1                      # (B,)
    qf = q.astype(jnp.float32)

    def slot_scores(qb, kb):
        # (K, G, hd) x (bs, K, hd) -> (K, G, bs): batch K, contract hd
        return jax.lax.dot_general(qb, kb, (((2,), (2,)), ((0,), (1,))),
                                   preferred_element_type=jnp.float32)

    def slot_out(pb, vb):
        # (K, G, bs) x (bs, K, hd) -> (K, G, hd): batch K, contract bs
        return jax.lax.dot_general(pb, vb, (((2,), (0,)), ((0,), (1,))),
                                   preferred_element_type=jnp.float32)

    def block_step(carry, inputs):
        m_run, l_run, acc = carry
        mi, k_blk, v_blk = inputs                # (B, bs, K, hd)
        start = mi * bs
        pos = start + jnp.arange(bs, dtype=jnp.int32)
        mask = pos[None, :] < cl[:, None]                         # (B, bs)
        live = start < cl                                         # (B,)
        if window > 0:
            mask &= pos[None, :] >= (cl - window)[:, None]
            live &= start + bs > cl - window
        s = jax.vmap(slot_scores)(qf, k_blk.astype(jnp.float32)) * scale
        s = jnp.where(mask[:, None, None, :], s, neg_inf)
        m_new = jnp.maximum(m_run, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1)
        a_new = acc * corr[..., None] + jax.vmap(slot_out)(
            p, v_blk.astype(jnp.float32))
        keep = live[:, None, None]
        return (jnp.where(keep, m_new, m_run),
                jnp.where(keep, l_new, l_run),
                jnp.where(keep[..., None], a_new, acc)), None

    m0 = jnp.full((B, K, G), neg_inf, jnp.float32)
    l0 = jnp.zeros((B, K, G), jnp.float32)
    a0 = jnp.zeros((B, K, G, hd), jnp.float32)
    kb = jnp.moveaxis(k_view.reshape(B, M, bs, K, hd), 1, 0)
    vb = jnp.moveaxis(v_view.reshape(B, M, bs, K, hd), 1, 0)
    (m_f, l_f, acc), _ = jax.lax.scan(
        block_step, (m0, l0, a0), (jnp.arange(M, dtype=jnp.int32), kb, vb))
    return (acc / jnp.maximum(l_f, 1e-20)[..., None]).astype(q.dtype)


def latent_decode_ref(q, view, lengths, *, r: int, scale: float,
                      chunk: int):
    """Gather-path oracle for ``kernels.latent_attention``: the kernel's
    chunked online softmax (bf16 operands, f32 accumulation) over the
    MATERIALISED logical view of each slot's latent rows.

    q: (B, H, D); view: (B, M*bs, D) gathered rows; lengths: (B,) int32
    (new row already written at ``lengths[b]``); ``chunk``: positions per
    kernel step (pages x block size). Returns (B, H, r) in q's dtype."""
    B, H, D = q.shape
    S = view.shape[1]
    pad = -S % chunk
    view = jnp.pad(view, ((0, 0), (0, pad), (0, 0)))
    n = (S + pad) // chunk
    cl = jnp.asarray(lengths, jnp.int32) + 1                      # (B,)
    neg_inf = -1e30

    def step(carry, inputs):
        m_run, l_run, acc = carry
        ci, kv = inputs                                           # (B, chunk, D)
        s = jnp.einsum("bhd,bcd->bhc", q, kv,
                       preferred_element_type=jnp.float32) * scale
        pos = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where(pos[None, None, :] < cl[:, None, None], s, neg_inf)
        m_new = jnp.maximum(m_run, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + p.sum(axis=-1, keepdims=True)
        a_new = acc * corr + jnp.einsum(
            "bhc,bcr->bhr", p.astype(kv.dtype), kv[..., :r],
            preferred_element_type=jnp.float32)
        live = (ci * chunk < cl)[:, None, None]
        return (jnp.where(live, m_new, m_run), jnp.where(live, l_new, l_run),
                jnp.where(live, a_new, acc)), None

    m0 = jnp.full((B, H, 1), neg_inf, jnp.float32)
    l0 = jnp.zeros((B, H, 1), jnp.float32)
    a0 = jnp.zeros((B, H, r), jnp.float32)
    kvs = jnp.moveaxis(view.reshape(B, n, chunk, D), 1, 0)
    (_, l_f, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n, dtype=jnp.int32), kvs))
    return (acc / jnp.maximum(l_f, 1e-20)).astype(q.dtype)


def rg_lru_ref(a, b, h0):
    """Sequential linear recurrence h_t = a_t h_{t-1} + b_t.
    a, b: (B, S, D) f32; h0: (B, D) f32. Returns (h_all (B,S,D), h_last)."""
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h
    h_last, hs = jax.lax.scan(step, h0,
                              (jnp.swapaxes(a, 0, 1), jnp.swapaxes(b, 0, 1)))
    return jnp.swapaxes(hs, 0, 1), h_last
