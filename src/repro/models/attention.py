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
from repro.models.layers import apply_rope, dense, init_dense

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
                      kv_valid_len=None, q_block=512, kv_block=512):
    """Flash-style attention without materialising (Sq, Skv) for full seqs.

    q: (B, Sq, H, hd); k, v: (B, Skv, K, hd) with H % K == 0 (GQA).
    q_offset: absolute position of q[0] (for decode / continued prefill).
    kv_valid_len: optional scalar — keys at positions >= this are masked.
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    _, Skv, K, _ = k.shape
    G = H // K
    scale = 1.0 / math.sqrt(hd)

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
# MLA (DeepSeek-V2) — compressed-latent KV cache
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    keys = jax.random.split(key, 6)
    p = {
        "w_dkv": init_dense(keys[0], d, m.kv_lora_rank),          # KV down-proj
        "w_krope": init_dense(keys[1], d, m.rope_head_dim),       # shared rope key
        "w_uk": init_dense(keys[2], m.kv_lora_rank, H * m.nope_head_dim),
        "w_uv": init_dense(keys[3], m.kv_lora_rank, H * m.v_head_dim),
        "w_q": init_dense(keys[4], d, H * (m.nope_head_dim + m.rope_head_dim)),
        "wo": init_dense(keys[5], H * m.v_head_dim, d),
    }
    return p


def _mla_qkv(params, cfg, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q = dense(params["w_q"], x).reshape(B, S, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    c_kv = dense(params["w_dkv"], x)                               # (B,S,r)
    k_rope = dense(params["w_krope"], x).reshape(B, S, 1, m.rope_head_dim)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand(params, cfg, c_kv):
    m = cfg.mla
    B, S, _ = c_kv.shape
    H = cfg.num_heads
    k_nope = dense(params["w_uk"], c_kv).reshape(B, S, H, m.nope_head_dim)
    v = dense(params["w_uv"], c_kv).reshape(B, S, H, m.v_head_dim)
    return k_nope, v


def mla_attention(params, cfg: ModelConfig, x, positions, *, window=0):
    """Train/prefill MLA. Concatenated (nope‖rope) q/k fed to the shared
    chunked-attention core; the rope key is broadcast across heads."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, cfg, x, positions)
    k_nope, v = _mla_expand(params, cfg, c_kv)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (B, S, H, m.rope_head_dim))], axis=-1)
    # pad v to match head_dim for the shared core? core allows hd_v != hd_qk?
    # chunked_attention assumes same hd for q/k and v shape (..., hd): we pass
    # v with its own dim by calling the core with matching K=H (no GQA here).
    out = chunked_attention(q, k, _pad_like(v, q.shape[-1]),
                            causal=True, window=window)[..., :m.v_head_dim]
    return dense(params["wo"], out.reshape(B, S, H * m.v_head_dim))


def _pad_like(v, hd):
    if v.shape[-1] == hd:
        return v
    return jnp.pad(v, ((0, 0),) * (v.ndim - 1) + ((0, hd - v.shape[-1]),))


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
    }


def mla_prefill(params, cfg: ModelConfig, x, positions, cache, *, window=0):
    out = mla_attention(params, cfg, x, positions, window=window)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, cfg, x, positions)
    cache = dict(cache)
    cache["c_kv"] = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0))
    cache["k_rope"] = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope[:, :, 0].astype(cache["k_rope"].dtype), (0, 0, 0))
    return out, cache


def mla_decode(params, cfg: ModelConfig, x, cache, cache_len, *, window=0):
    """Decode with the compressed cache, expanding K/V on the fly."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    positions = jnp.full((B, 1), cache_len, jnp.int32)
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(params, cfg, x, positions)
    cache = dict(cache)
    cache["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv_new.astype(cache["c_kv"].dtype), cache_len, axis=1)
    cache["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope_new[:, :, 0].astype(cache["k_rope"].dtype),
        cache_len, axis=1)
    S_max = cache["c_kv"].shape[1]
    k_nope, v = _mla_expand(params, cfg, cache["c_kv"].astype(x.dtype))
    k_rope_all = jnp.broadcast_to(cache["k_rope"][:, :, None, :].astype(x.dtype),
                                  (B, S_max, H, m.rope_head_dim))
    k = jnp.concatenate([k_nope, k_rope_all], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1).reshape(B, 1, H, -1)
    out = decode_attention(q, k, _pad_like(v, q.shape[-1]),
                           cache_len=cache_len + 1, window=window)
    out = out[..., :m.v_head_dim]
    return dense(params["wo"], out.reshape(B, 1, H * m.v_head_dim)), cache
