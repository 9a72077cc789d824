"""Attention: GQA (optional bias, RoPE, full / sliding-window) and
DeepSeek-style MLA (multi-head latent attention, compressed KV cache).

Prefill/train uses a chunked online-softmax (flash-style) implementation in
pure JAX (``lax.scan`` over KV blocks) so the S x S score matrix is never
materialised — required for the 32k-prefill shapes to fit HBM.
Decode (Sq == 1) attends directly over the cache.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.layers import (apply_rope, dense, init_dense, init_rmsnorm,
                                 rmsnorm, yarn_mscale)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# chunked online-softmax attention core
# ---------------------------------------------------------------------------

def _block_mask(q_pos, k_pos, causal: bool, window: int):
    """(Sq_blk, Skv_blk) boolean mask. window==0 -> full causal."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m


def chunked_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                      kv_valid_len=None, q_block=512, kv_block=512,
                      scale=None):
    """Flash-style attention without materialising (Sq, Skv) for full seqs.

    q: (B, Sq, H, hd); k, v: (B, Skv, K, hd) with H % K == 0 (GQA).
    q_offset: absolute position of q[0] (for decode / continued prefill).
    kv_valid_len: optional scalar — keys at positions >= this are masked.
    scale: softmax scale of the scores (default ``hd ** -0.5``).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    scale = 1.0 / math.sqrt(hd) if scale is None else scale

    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Skv)
    # pad to block multiples
    pq = (-Sq) % q_block
    pkv = (-Skv) % kv_block
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pkv:
        k = jnp.pad(k, ((0, 0), (0, pkv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pkv), (0, 0), (0, 0)))
    Sq_p, Skv_p = Sq + pq, Skv + pkv
    nq, nkv = Sq_p // q_block, Skv_p // kv_block

    # reshape to blocks; put head grouping explicit for GQA
    qb = q.reshape(B, nq, q_block, K, G, hd)
    kb = k.reshape(B, nkv, kv_block, K, hd)
    vb = v.reshape(B, nkv, kv_block, K, hd)

    valid = Skv if kv_valid_len is None else kv_valid_len

    def per_q_block(qi, q_blk):
        # q_blk: (B, q_block, K, G, hd)
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)

        def kv_step(carry, inputs):
            m_run, l_run, acc = carry
            ki, k_blk, v_blk = inputs
            k_pos = ki * kv_block + jnp.arange(kv_block)
            s = jnp.einsum("bqkgh,bckh->bkgqc", q_blk, k_blk) * scale
            mask = _block_mask(q_pos, k_pos, causal, window)
            mask &= (k_pos < valid)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bckh->bkgqh", p.astype(v_blk.dtype), v_blk
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, K, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, K, G, q_block, hd), jnp.float32)
        ks = jnp.arange(nkv)
        (m_f, l_f, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (ks, jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0)))
        out = (acc / jnp.maximum(l_f, 1e-20)[..., None]).astype(q.dtype)
        # (B, K, G, q_block, hd) -> (B, q_block, K, G, hd)
        return jnp.transpose(out, (0, 3, 1, 2, 4))

    outs = jax.lax.map(lambda args: per_q_block(*args),
                       (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq_p, H, hd)
    return out[:, :Sq]


def decode_attention(q, k_cache, v_cache, *, cache_len, window=0):
    """Single-token attention over a cache. q: (B, 1, H, hd);
    k_cache/v_cache: (B, S_max, K, hd); cache_len: current length (incl. new
    token) — a scalar, or a (B,) vector for continuous batching where every
    slot sits at its own position."""
    B, _, H, hd = q.shape
    _, S_max, K, _ = k_cache.shape
    G = H // K
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache) * scale
    pos = jnp.arange(S_max)
    cl = jnp.reshape(jnp.asarray(cache_len, jnp.int32), (-1, 1))  # (1|B, 1)
    mask = pos[None, :] < cl                                      # (1|B, S)
    if window > 0:
        mask = mask & (pos[None, :] >= cl - window)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    # keep the probs in f32 for the PV product (matches the paged fused
    # kernel's f32 accumulator, so linear and paged decode agree to
    # summation-order noise instead of bf16-cast noise)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype).reshape(B, 1, H, hd)


# ---------------------------------------------------------------------------
# GQA module
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, d_model=None, num_heads=None, num_kv=None):
    d = d_model or cfg.d_model
    H = num_heads or cfg.num_heads
    K = num_kv or cfg.num_kv_heads
    hd = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": init_dense(k1, d, H * hd, bias=cfg.qkv_bias),
        "wk": init_dense(k2, d, K * hd, bias=cfg.qkv_bias),
        "wv": init_dense(k3, d, K * hd, bias=cfg.qkv_bias),
        "wo": init_dense(k4, H * hd, d),
    }


def gqa_project(params, cfg: ModelConfig, x, positions, num_heads=None, num_kv=None):
    B, S, _ = x.shape
    H = num_heads or cfg.num_heads
    K = num_kv or cfg.num_kv_heads
    hd = cfg.head_dim
    q = dense(params["wq"], x).reshape(B, S, H, hd)
    k = dense(params["wk"], x).reshape(B, S, K, hd)
    v = dense(params["wv"], x).reshape(B, S, K, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attention(params, cfg: ModelConfig, x, positions, *, window=0,
                  num_heads=None, num_kv=None):
    """Train/prefill self-attention (causal)."""
    q, k, v = gqa_project(params, cfg, x, positions, num_heads, num_kv)
    out = chunked_attention(q, k, v, causal=True, window=window)
    B, S = x.shape[:2]
    return dense(params["wo"], out.reshape(B, S, -1))


def gqa_prefill(params, cfg: ModelConfig, x, positions, cache, *, window=0):
    """Prefill: run attention AND write k/v into the cache (from position 0)."""
    with jax.named_scope("attn.qkv"):
        q, k, v = gqa_project(params, cfg, x, positions)
    with jax.named_scope("attn.causal"):
        out = chunked_attention(q, k, v, causal=True, window=window)
    B, S = x.shape[:2]
    cache = dict(cache)
    with jax.named_scope("attn.kv_write"):
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
    with jax.named_scope("attn.out"):
        return dense(params["wo"], out.reshape(B, S, -1)), cache


def gqa_decode(params, cfg: ModelConfig, x, cache, cache_len, *, window=0):
    """Decode one token. x: (B, 1, d). cache_len: length BEFORE this token."""
    B = x.shape[0]
    positions = jnp.full((B, 1), cache_len, jnp.int32)
    q, k, v = gqa_project(params, cfg, x, positions)
    cache = dict(cache)
    # write new kv at cache_len
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), cache_len, axis=1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), cache_len, axis=1)
    out = decode_attention(q, cache["k"], cache["v"],
                           cache_len=cache_len + 1, window=window)
    return dense(params["wo"], out.reshape(B, 1, -1)), cache


def gqa_decode_multi(params, cfg: ModelConfig, x, cache, lengths, *, window=0):
    """Continuous-batching decode over a slotted linear cache.

    Every slot decodes at its OWN position: x: (B, 1, d); cache k/v:
    (B, S_max, K, hd); lengths: (B,) int32 current length per slot (the new
    token is written at ``lengths[b]``). Inactive slots decode garbage that
    the caller masks out; their cache writes land at their own (stale)
    position and are overwritten when the slot is re-prefilled.
    """
    B = x.shape[0]
    positions = jnp.asarray(lengths, jnp.int32)[:, None]          # (B, 1)
    q, k, v = gqa_project(params, cfg, x, positions)
    cache = dict(cache)
    b_idx = jnp.arange(B)
    cache["k"] = cache["k"].at[b_idx, positions[:, 0]].set(
        k[:, 0].astype(cache["k"].dtype))
    cache["v"] = cache["v"].at[b_idx, positions[:, 0]].set(
        v[:, 0].astype(cache["v"].dtype))
    out = decode_attention(q, cache["k"], cache["v"],
                           cache_len=lengths + 1, window=window)
    return dense(params["wo"], out.reshape(B, 1, -1)), cache


def gqa_decode_paged(params, cfg: ModelConfig, x, pool, block_tables, lengths,
                     *, window: int = 0, mesh=None):
    """Continuous-batching decode over a paged KV block pool.

    pool k/v: (N_blocks, block, K, hd) — one shared fixed-shape pool, so
    jit never recompiles as requests join/leave. block_tables: (B, M) int32
    maps each slot's logical block m to a physical block (entries beyond a
    slot's allocation point at the reserved null block 0 and are masked by
    ``lengths``). lengths: (B,) — the new token is written at logical
    position ``lengths[b]``, whose physical block MUST already be allocated
    (the scheduler grows tables before calling); ``lengths[b] == 0`` marks
    a released/idle slot whose KV write is suppressed so dead slots never
    dirty the null block. ``window``: architectural sliding window, applied
    as a mask (blocks stay allocated — the pool is linear in logical
    positions; correctness first, reclaim later).

    ``cfg.paged_attn_impl`` selects the attention path: "fused" runs the
    Pallas kernel straight off the pool (no gathered intermediate);
    "gather" materializes the logical view and runs the identical blockwise
    online-softmax in pure jnp (fp32 bit-exact oracle). ``mesh``: the
    serving mesh, under which the fused kernel runs in a ``shard_map``.
    """
    from repro.kernels import ops as _kernel_ops
    from repro.kernels import ref as _kernel_ref

    B = x.shape[0]
    N, bs, K, hd = pool["k"].shape
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None]                                  # (B, 1)
    with jax.named_scope("attn.qkv"):
        q, k, v = gqa_project(params, cfg, x, positions)
    with jax.named_scope("attn.kv_write"):
        b_idx = jnp.arange(B)
        blk = block_tables[b_idx, positions[:, 0] // bs]          # (B,)
        off = positions[:, 0] % bs                                # (B,)
        # slots own disjoint blocks, so cross-slot collisions only happen
        # on the null block; inactive slots (lengths == 0 after release)
        # keep the old value — their table rows all point at the null
        # block, which must stay clean for every other slot's masked reads
        active = (lengths > 0)[:, None, None]                     # (B, 1, 1)
        k_pool = pool["k"].at[blk, off].set(
            jnp.where(active, k[:, 0].astype(pool["k"].dtype),
                      pool["k"][blk, off]))
        v_pool = pool["v"].at[blk, off].set(
            jnp.where(active, v[:, 0].astype(pool["v"].dtype),
                      pool["v"][blk, off]))
    G = q.shape[2] // K
    qg = q.reshape(B, K, G, hd)
    impl = getattr(cfg, "paged_attn_impl", "fused")
    with jax.named_scope("attn.paged"):
        if impl == "fused":
            out = _kernel_ops.paged_decode_attention(
                qg, k_pool, v_pool, block_tables, lengths, window=window,
                mesh=mesh)
        else:
            # gather each slot's view: (B, M, bs, K, hd) -> (B, M*bs, K, hd)
            k_view = k_pool[block_tables].reshape(B, -1, K, hd)
            v_view = v_pool[block_tables].reshape(B, -1, K, hd)
            out = _kernel_ref.paged_decode_ref(qg, k_view, v_view, lengths,
                                               window=window, block_size=bs)
    if mesh is not None:
        # pin the pool layout so every step sees the same input sharding
        # (each distinct layout would be its own compiled program)
        from jax.sharding import NamedSharding
        from repro.sharding import paged_pool_spec
        sh = NamedSharding(mesh, paged_pool_spec(mesh, K, stacked=False))
        k_pool = jax.lax.with_sharding_constraint(k_pool, sh)
        v_pool = jax.lax.with_sharding_constraint(v_pool, sh)
    pool = {**pool, "k": k_pool, "v": v_pool}
    with jax.named_scope("attn.out"):
        return dense(params["wo"], out.reshape(B, 1, -1)), pool


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
                   num_kv=None):
    K = num_kv or cfg.num_kv_heads
    shape = (batch, max_len, K, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# Rotating-window caches (sliding-window archs: cache buffer == window size,
# slot = absolute_position % window; RoPE is applied at absolute positions at
# write time so relative attention is preserved regardless of slot order).
# ---------------------------------------------------------------------------

def gqa_prefill_windowed(params, cfg: ModelConfig, x, positions, cache, *,
                         window: int):
    """Prefill with a rotating window cache (buffer length == window)."""
    W = cache["k"].shape[1]
    if W > window:
        return gqa_prefill(params, cfg, x, positions, cache, window=window)
    q, k, v = gqa_project(params, cfg, x, positions)
    out = chunked_attention(q, k, v, causal=True, window=window)
    B, S = x.shape[:2]
    n = min(S, W)
    tail_pos = np.arange(S - n, S)
    slots = tail_pos % W
    cache = dict(cache)
    cache["k"] = cache["k"].at[:, slots].set(k[:, tail_pos].astype(cache["k"].dtype))
    cache["v"] = cache["v"].at[:, slots].set(v[:, tail_pos].astype(cache["v"].dtype))
    return dense(params["wo"], out.reshape(B, S, -1)), cache


def gqa_decode_windowed(params, cfg: ModelConfig, x, cache, cache_len, *,
                        window: int = 0):
    """Decode against either a linear cache (window == 0 or full-length
    buffer) or a rotating window buffer."""
    W = cache["k"].shape[1]
    if window == 0 or W > window:
        return gqa_decode(params, cfg, x, cache, cache_len, window=window)
    B = x.shape[0]
    positions = jnp.full((B, 1), cache_len, jnp.int32)
    q, k, v = gqa_project(params, cfg, x, positions)
    slot = cache_len % W
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
    valid = jnp.minimum(cache_len + 1, W)          # buffer only holds window
    out = decode_attention(q, cache["k"], cache["v"], cache_len=valid, window=0)
    return dense(params["wo"], out.reshape(B, 1, -1)), cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2) — multi-head latent attention over a latent cache
# ---------------------------------------------------------------------------
#
# The published block (``DeepseekV2Attention``, no q LoRA): q = x W_q split
# into (q_nope, q_pe) per head; ``kv_a_proj_with_mqa`` gives the latent c
# (kv_lora_rank wide, then RMSNorm'd) and one shared k_pe; ``kv_b_proj``
# expands c into per-head k_nope (``w_uk``) and v (``w_uv``); RoPE (YaRN)
# on q_pe and k_pe; softmax scale (qk head dim)^-0.5 x mscale^2.
#
# The cache keeps one row per token: [c, roped k_pe], zero-padded to a
# multiple of 128 lanes (``mla_row_width``; the TPU tiles a row's minor
# dim to 128 lanes either way, so the pad costs no memory, and the paged
# kernel's DMA moves whole tiles). Prefill attends in the expanded form;
# decode in the absorbed one: q_nope . W_uk^T joins q_pe as a query over
# the rows, and W_uv is applied to the attended latent after.

def mla_row_width(cfg: ModelConfig) -> int:
    m = cfg.mla
    return -(-(m.kv_lora_rank + m.rope_head_dim) // 128) * 128


def mla_softmax_scale(cfg: ModelConfig) -> float:
    m = cfg.mla
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    keys = jax.random.split(key, 5)
    return {
        "w_q": init_dense(keys[0], d, H * (m.nope_head_dim + m.rope_head_dim)),
        # kv_a_proj_with_mqa: the latent, then the shared rope key
        "w_kv_a": init_dense(keys[1], d, m.kv_lora_rank + m.rope_head_dim),
        "kv_norm": init_rmsnorm(m.kv_lora_rank),
        # kv_b_proj, split into its key and value halves
        "w_uk": init_dense(keys[2], m.kv_lora_rank, H * m.nope_head_dim),
        "w_uv": init_dense(keys[3], m.kv_lora_rank, H * m.v_head_dim),
        "wo": init_dense(keys[4], H * m.v_head_dim, d),
    }


def _mla_query(params, cfg, x, positions):
    """(q_nope (B,S,H,nope), roped q_pe (B,S,H,rope))."""
    m = cfg.mla
    B, S, _ = x.shape
    q = dense(params["w_q"], x).reshape(
        B, S, cfg.num_heads, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_pe = jnp.split(q, [m.nope_head_dim], axis=-1)
    return q_nope, apply_rope(q_pe, positions, cfg.rope_theta, cfg.yarn)


def _mla_rows(params, cfg, x, positions):
    """(B, S, mla_row_width) cache rows: the normed latent, the roped
    shared key, zeros."""
    m = cfg.mla
    B, S, _ = x.shape
    kv = dense(params["w_kv_a"], x)
    c, k_pe = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c = rmsnorm(params["kv_norm"], c)
    k_pe = apply_rope(k_pe[:, :, None], positions, cfg.rope_theta,
                      cfg.yarn)[:, :, 0]
    pad = mla_row_width(cfg) - m.kv_lora_rank - m.rope_head_dim
    return jnp.concatenate([c, k_pe, jnp.zeros((B, S, pad), c.dtype)], -1)


def _mla_expanded(params, cfg, x, positions, window):
    """Causal self-attention in the expanded form. Returns the attention
    output before ``wo``, (B, S, H * v), and the cache rows."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_pe = _mla_query(params, cfg, x, positions)
    rows = _mla_rows(params, cfg, x, positions)
    c = rows[..., :m.kv_lora_rank]
    k_pe = rows[..., m.kv_lora_rank:m.kv_lora_rank + m.rope_head_dim]
    k_nope = dense(params["w_uk"], c).reshape(B, S, H, m.nope_head_dim)
    v = dense(params["w_uv"], c).reshape(B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None], (B, S, H, m.rope_head_dim))], axis=-1)
    out = chunked_attention(q, k, _pad_like(v, q.shape[-1]), causal=True,
                            window=window, scale=mla_softmax_scale(cfg))
    return out[..., :m.v_head_dim].reshape(B, S, H * m.v_head_dim), rows


def _pad_like(v, hd):
    if v.shape[-1] == hd:
        return v
    return jnp.pad(v, ((0, 0),) * (v.ndim - 1) + ((0, hd - v.shape[-1]),))


def mla_attention(params, cfg: ModelConfig, x, positions, *, window=0):
    """Train/prefill MLA (causal)."""
    out, _ = _mla_expanded(params, cfg, x, positions, window)
    return dense(params["wo"], out)


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16):
    return {"latent": jnp.zeros((batch, max_len, mla_row_width(cfg)), dtype)}


def mla_prefill(params, cfg: ModelConfig, x, positions, cache, *, window=0):
    """Prefill: attend in the expanded form AND write the latent rows into
    the cache (from position 0)."""
    with jax.named_scope("attn.causal"):
        out, rows = _mla_expanded(params, cfg, x, positions, window)
    cache = dict(cache)
    with jax.named_scope("attn.kv_write"):
        cache["latent"] = jax.lax.dynamic_update_slice(
            cache["latent"], rows.astype(cache["latent"].dtype), (0, 0, 0))
    with jax.named_scope("attn.out"):
        return dense(params["wo"], out), cache


def _mla_absorbed_query(params, cfg, x, positions):
    """(B, H, mla_row_width) decode queries over the rows: q_nope . W_uk^T,
    then the roped q_pe, then zeros. x: (B, 1, d)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    with jax.named_scope("attn.qkv"):
        q_nope, q_pe = _mla_query(params, cfg, x, positions)
    with jax.named_scope("attn.absorb"):
        w_uk = params["w_uk"]["w"].reshape(m.kv_lora_rank, H, m.nope_head_dim)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk.astype(x.dtype))
        pad = mla_row_width(cfg) - m.kv_lora_rank - m.rope_head_dim
        return jnp.concatenate(
            [q_lat, q_pe[:, 0], jnp.zeros((B, H, pad), x.dtype)], -1)


def _mla_absorbed_out(params, cfg, lat):
    """Attended latent (B, H, r) -> (B, 1, d): W_uv, then ``wo``."""
    m = cfg.mla
    B, H, _ = lat.shape
    with jax.named_scope("attn.absorb"):
        w_uv = params["w_uv"]["w"].reshape(m.kv_lora_rank, H, m.v_head_dim)
        o = jnp.einsum("bhr,rhv->bhv", lat, w_uv.astype(lat.dtype))
    with jax.named_scope("attn.out"):
        return dense(params["wo"], o.reshape(B, 1, H * m.v_head_dim))


def mla_decode(params, cfg: ModelConfig, x, cache, cache_len, *, window=0):
    """Decode one token over a linear latent cache, absorbed form. x: (B,
    1, d); cache_len: length BEFORE this token (a scalar)."""
    m = cfg.mla
    B = x.shape[0]
    positions = jnp.full((B, 1), cache_len, jnp.int32)
    q = _mla_absorbed_query(params, cfg, x, positions)
    cache = dict(cache)
    rows = _mla_rows(params, cfg, x, positions)
    cache["latent"] = jax.lax.dynamic_update_slice_in_dim(
        cache["latent"], rows.astype(cache["latent"].dtype), cache_len, axis=1)
    lat = cache["latent"].astype(x.dtype)                        # (B, S, D)
    s = jnp.einsum("bhd,bsd->bhs", q, lat,
                   preferred_element_type=jnp.float32)
    s = s * mla_softmax_scale(cfg)
    pos = jnp.arange(lat.shape[1])
    mask = pos < cache_len + 1
    if window > 0:
        mask = mask & (pos >= cache_len + 1 - window)
    p = jax.nn.softmax(jnp.where(mask[None, None], s, NEG_INF), axis=-1)
    out = jnp.einsum("bhs,bsr->bhr", p, lat[..., :m.kv_lora_rank]
                     .astype(jnp.float32)).astype(x.dtype)
    return _mla_absorbed_out(params, cfg, out), cache


def mla_decode_paged(params, cfg: ModelConfig, x, pool, block_tables,
                     lengths, *, mesh=None):
    """Continuous-batching decode over a paged latent pool, absorbed form.

    pool["latent"]: (N_blocks, block, mla_row_width) — one row per token
    (block 0 the reserved null block); block_tables, lengths and the
    inactive-slot rule as in ``gqa_decode_paged``. ``cfg.paged_attn_impl``
    "fused" runs the Pallas latent kernel (``kernels.latent_attention``)
    straight off the pool, "gather" its jnp oracle over the gathered view.
    """
    from repro.kernels import latent_attention as _latent
    from repro.kernels import ops as _kernel_ops
    from repro.kernels import ref as _kernel_ref

    m = cfg.mla
    B = x.shape[0]
    lat_pool = pool["latent"]
    N, bs, D = lat_pool.shape
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None]                                  # (B, 1)
    q = _mla_absorbed_query(params, cfg, x, positions)
    with jax.named_scope("attn.kv_write"):
        rows = _mla_rows(params, cfg, x, positions)[:, 0]
        b_idx = jnp.arange(B)
        blk = block_tables[b_idx, positions[:, 0] // bs]          # (B,)
        off = positions[:, 0] % bs
        active = (lengths > 0)[:, None]
        lat_pool = lat_pool.at[blk, off].set(
            jnp.where(active, rows.astype(lat_pool.dtype), lat_pool[blk, off]))
    scale = mla_softmax_scale(cfg)
    with jax.named_scope("attn.latent"):
        if getattr(cfg, "paged_attn_impl", "fused") == "fused":
            out = _kernel_ops.latent_decode_attention(
                q, lat_pool, block_tables, lengths, r=m.kv_lora_rank,
                scale=scale, mesh=mesh)
        else:
            out = _kernel_ref.latent_decode_ref(
                q, lat_pool[block_tables].reshape(B, -1, D), lengths,
                r=m.kv_lora_rank, scale=scale,
                chunk=_latent.PAGES_PER_STEP * bs)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        lat_pool = jax.lax.with_sharding_constraint(
            lat_pool, NamedSharding(mesh, P()))
    return _mla_absorbed_out(params, cfg, out), {**pool, "latent": lat_pool}
