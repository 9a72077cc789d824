"""Core neural building blocks (pure-functional: init_* -> params dict,
apply functions take params explicitly).

Conventions
-----------
* params are nested dicts of jnp arrays; leaves are float32 at init and
  cast to the compute dtype inside apply (weights stay in param dtype,
  activations in ``cfg`` compute dtype — callers pass already-cast params
  when running bf16).
* all apply fns are shape-polymorphic over leading batch dims.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def truncated_normal_init(key, shape, scale=0.02, dtype=jnp.float32):
    return scale * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * params["scale"]).astype(dtype)


def nonparametric_layernorm(x, eps: float = 1e-5):
    """OLMo-style LN without learnable affine parameters [arXiv:2402.00838]."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)).astype(dtype)


def init_norm(kind: str, d: int):
    if kind == "rmsnorm":
        return init_rmsnorm(d)
    if kind == "nonparametric":
        return {}
    raise ValueError(kind)


def apply_norm(kind: str, params, x):
    if kind == "rmsnorm":
        return rmsnorm(params, x)
    if kind == "nonparametric":
        return nonparametric_layernorm(x)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Linear / embedding
# ---------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, bias: bool = False, scale: float = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {"w": truncated_normal_init(key, (d_in, d_out), scale)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense(params, x):
    y = jnp.einsum("...i,io->...o", x, params["w"].astype(x.dtype))
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


def init_embedding(key, vocab: int, d: int):
    return {"table": truncated_normal_init(key, (vocab, d), 0.02)}


def embed(params, ids):
    return jnp.take(params["table"], ids, axis=0)


def unembed(params, x):
    """Tied unembedding from an embedding table."""
    return jnp.einsum("...d,vd->...v", x, params["table"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float = 10000.0, yarn=None):
    """Inverse frequencies of the rotary pairs, (head_dim/2,). ``yarn``: a
    ``configs.base.YarnConfig`` — DeepSeek-V2's YaRN scaling
    (``DeepseekV2YarnRotaryEmbedding``): pairs that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    pairs that turn fewer than ``beta_slow`` times are slowed by
    ``factor``, with a linear ramp between the two."""
    base = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))
    if yarn is None:
        return base
    def dim_of(turns):
        return (head_dim * math.log(yarn.original_max_position
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                    # 1: extrapolate, 0: interpolate
    return base / yarn.factor * (1.0 - keep) + base * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float = 10000.0, yarn=None):
    """x: (..., seq, heads, head_dim); positions: (..., seq). Rotate-half
    pairing (first and second half of each head). ``yarn``: YaRN-scaled
    frequencies, and cos/sin scaled by ``mscale(mscale) /
    mscale(mscale_all_dim)`` (1 where the two are equal)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta, yarn)                # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs   # (..., seq, hd/2)
    angles = angles[..., None, :]                                  # (..., seq, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        ratio = (yarn_mscale(yarn.factor, yarn.mscale)
                 / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if ratio != 1.0:
            cos, sin = cos * ratio, sin * ratio
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------

def init_ffn(key, d_model: int, d_ff: int, activation: str):
    k1, k2, k3 = jax.random.split(key, 3)
    if activation == "swiglu":
        return {
            "w_gate": truncated_normal_init(k1, (d_model, d_ff), 1 / math.sqrt(d_model)),
            "w_up": truncated_normal_init(k2, (d_model, d_ff), 1 / math.sqrt(d_model)),
            "w_down": truncated_normal_init(k3, (d_ff, d_model), 1 / math.sqrt(d_ff)),
        }
    return {
        "w_up": truncated_normal_init(k1, (d_model, d_ff), 1 / math.sqrt(d_model)),
        "w_down": truncated_normal_init(k2, (d_ff, d_model), 1 / math.sqrt(d_ff)),
    }


def ffn(params, x, activation: str):
    if activation == "swiglu":
        g = jnp.einsum("...d,df->...f", x, params["w_gate"].astype(x.dtype))
        u = jnp.einsum("...d,df->...f", x, params["w_up"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jnp.einsum("...d,df->...f", x, params["w_up"].astype(x.dtype))
        if activation == "gelu":
            h = jax.nn.gelu(h)
        elif activation == "relu":
            h = jax.nn.relu(h)
        elif activation == "relu2":
            h = jnp.square(jax.nn.relu(h))
        else:
            raise ValueError(activation)
    return jnp.einsum("...f,fd->...d", h, params["w_down"].astype(x.dtype))
