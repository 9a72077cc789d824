"""Plain float32 reference of the configuration, and its fp8 control.

A straightforward ``jax.numpy`` forward of a Mixtral-style decoder under
``jax.default_matmul_precision("highest")``: RMSNorm, rotary GQA causal
attention over the whole sequence, a softmax router whose top-k gates are
renormalised, SwiGLU experts, an untied LM head. No kernel, no cache, no
batching of requests into slots. It regenerates the weights itself from
the seed (``weights.py``), one layer and one expert at a time, so it fits
beside nothing else on the chip, and imports nothing of the program.

``teacher_forced`` runs each sequence (prompt + served tokens) once and
returns, for every served token, how far its logit lies below the
reference's best. With ``control=True`` it also runs the same forward
with every projection weight rounded to fp8 (e4m3, one scale per output
channel) and returns the gap of the token the fp8 forward puts first:
the lower-precision step a later change might be tempted to take.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

BUCKET = 128


def _f32(x):
    return x.astype(jnp.float32)


def fp8_round(w):
    """Weights (in, out) rounded to float8_e4m3fn, scale per output column."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _maybe_q(w, control):
    return fp8_round(w) if control else w


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (S, n, hd); rotate-half pairing at positions 0..S-1."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs        # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("D", "eps", "theta", "control"))
def _attention(x, n_real, t, D, eps, theta, control):
    """x: (S, d) residual stream of one sequence (rows >= n_real padding)."""
    D = dict(D)
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        H, K, hd = D["H"], D["K"], D["hd"]
        h = rms(x, _f32(t["ln1"]), eps)
        q = (h @ _maybe_q(_f32(t["wq"]), control)).reshape(S, H, hd)
        k = (h @ _maybe_q(_f32(t["wk"]), control)).reshape(S, K, hd)
        v = (h @ _maybe_q(_f32(t["wv"]), control)).reshape(S, K, hd)
        q, k = rope(q, theta), rope(k, theta)
        G = H // K
        k = jnp.repeat(k, G, axis=1)                # head h reads kv h // G
        v = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        pos = jnp.arange(S)
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_real)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
        return x + o @ _maybe_q(_f32(t["wo"]), control)


@partial(jax.jit, static_argnames=("D", "eps"))
def _route(x, t, D, eps):
    """Normed input of the expert layer and each token's gate per expert
    (zero for experts outside its top k)."""
    D = dict(D)
    with jax.default_matmul_precision("highest"):
        h = rms(x, _f32(t["ln2"]), eps)
        probs = jax.nn.softmax(h @ _f32(t["router"]), axis=-1)
        top, idx = jax.lax.top_k(probs, D["top_k"])
        top = top / top.sum(-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(h.shape[0])[:, None], idx].set(top)
        return h, gates


@partial(jax.jit, static_argnames=("control",))
def _expert(y, h, gate, ew, control):
    with jax.default_matmul_precision("highest"):
        g = h @ _maybe_q(_f32(ew["w_gate"]), control)
        u = h @ _maybe_q(_f32(ew["w_up"]), control)
        return y + gate[:, None] * (
            (jax.nn.silu(g) * u) @ _maybe_q(_f32(ew["w_down"]), control))


@partial(jax.jit, static_argnames=("eps", "control"))
def _argmax(x, final_norm, head, eps, control):
    """Top token of each row's logits."""
    with jax.default_matmul_precision("highest"):
        lg = rms(x, _f32(final_norm), eps) @ _maybe_q(_f32(head), control)
        return jnp.argmax(lg, -1)


@partial(jax.jit, static_argnames=("eps",))
def _gaps(x, final_norm, head, eps, tokens):
    """Per row and per column of ``tokens`` (S, n): best logit minus the
    logit of that token."""
    with jax.default_matmul_precision("highest"):
        lg = rms(x, _f32(final_norm), eps) @ _f32(head)
        return lg.max(-1, keepdims=True) - jnp.take_along_axis(lg, tokens, 1)


def _forward(conf, root, seqs, control):
    """Residual streams after the last layer, one (S_pad, d) per sequence."""
    D = W.dims(conf)
    Dh = tuple(sorted(D.items()))
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    o = jax.jit(lambda r: W.outer_tensors(r, D))(root)
    xs = []
    for s in seqs:
        pad = -(-len(s) // BUCKET) * BUCKET
        ids = np.zeros((pad,), np.int32)
        ids[:len(s)] = s
        xs.append(_f32(o["embed"][jnp.asarray(ids)]))
    del o
    layer = jax.jit(lambda r, l: W.layer_tensors(r, D, l))
    expert = jax.jit(lambda r, l, e: W.expert_tensors(r, D, l, e))
    for l in range(D["L"]):
        t = layer(root, l)
        xs = [_attention(x, len(s), t, Dh, eps, theta, control)
              for x, s in zip(xs, seqs)]
        routed = [_route(x, t, Dh, eps) for x in xs]
        ys = [x for x in xs]
        for e in range(D["E"]):
            ew = expert(root, l, e)
            ys = [_expert(y, h, g[:, e], ew, control)
                  for y, (h, g) in zip(ys, routed)]
            del ew
        xs = ys
        del t, routed
    return xs


def _targets(x, s, n):
    """(S_pad,) served token each row predicts, and the rows that do:
    rows P-1 .. P+n-2 predict the served tokens 0..n-1."""
    P = len(s) - n
    t = np.zeros((x.shape[0],), np.int32)
    t[P - 1:P - 1 + n] = np.asarray(s[P:], np.int32)
    return t, slice(P - 1, P - 1 + n)


def teacher_forced(conf: dict, seed: int, seqs, n_served, *,
                   control: bool = False):
    """Gaps of served tokens below the reference's best logit.

    ``seqs``: int arrays, prompt followed by the served tokens;
    ``n_served``: how many of each are served (the last ones). Returns
    ``{"gap": array}`` with one entry per served token, and with
    ``control`` also ``"control_gap"``: at the same positions, the gap of
    the token the fp8 forward ranks first."""
    if not seqs:
        return {"gap": np.zeros(0), "control_gap": np.zeros(0)}
    root = W.root_key(seed)
    D = W.dims(conf)
    eps = float(conf["rms_norm_eps"])
    outer = jax.jit(lambda r: W.outer_tensors(r, D))
    picks = None
    if control:
        xs = _forward(conf, root, seqs, True)
        o = outer(root)
        picks = [np.asarray(_argmax(x, o["final_norm"], o["lm_head"], eps,
                                    True)) for x in xs]
        del xs, o
    xs = _forward(conf, root, seqs, False)
    o = outer(root)
    gap, cgap = [], []
    for i, (x, s, n) in enumerate(zip(xs, seqs, n_served)):
        t, rows = _targets(x, s, n)
        cols = [t] + ([picks[i].astype(np.int32)] if control else [])
        g = np.asarray(_gaps(x, o["final_norm"], o["lm_head"], eps,
                             jnp.asarray(np.stack(cols, 1))))
        gap.append(g[rows, 0])
        if control:
            cgap.append(g[rows, 1])
    out = {"gap": np.concatenate(gap or [np.zeros(0)])}
    if control:
        out["control_gap"] = np.concatenate(cgap or [np.zeros(0)])
    return out
