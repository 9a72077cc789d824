"""Plain float32 reference of a configuration, and its fp8 control.

A straightforward ``jax.numpy`` forward under
``jax.default_matmul_precision("highest")``: the embedding, the
architecture's layers (``forward_layers`` of ``bench/arch/<model_type>.py``,
built from the pieces here: ``rms``, ``rope``, ``fp8_round``), the final
RMSNorm and an untied LM head. No kernel, no cache, no batching of
requests into slots. The weights are regenerated from the seed
(``weights.py``), one layer and one expert at a time, so it fits beside
nothing else on the chip, and nothing of the program is imported.

``teacher_forced`` runs each sequence (prompt + served tokens) once and
returns, for every served token, how far its logit lies below the
reference's best. With ``control=True`` it also runs the same forward
with every projection weight rounded to fp8 (e4m3, one scale per output
channel) and returns the gap of the token the fp8 forward puts first:
the lower-precision step a later change might be tempted to take.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

BUCKET = 128


def f32(x):
    return x.astype(jnp.float32)


def fp8_round(w):
    """Weights (in, out) rounded to float8_e4m3fn, scale per output column."""
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def maybe_q(w, control):
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


@partial(jax.jit, static_argnames=("eps", "control"))
def _argmax(x, final_norm, head, eps, control):
    """Top token of each row's logits."""
    with jax.default_matmul_precision("highest"):
        lg = rms(x, f32(final_norm), eps) @ maybe_q(f32(head), control)
        return jnp.argmax(lg, -1)


@partial(jax.jit, static_argnames=("eps",))
def _gaps(x, final_norm, head, eps, tokens):
    """Per row and per column of ``tokens`` (S, n): best logit minus the
    logit of that token."""
    with jax.default_matmul_precision("highest"):
        lg = rms(x, f32(final_norm), eps) @ f32(head)
        return lg.max(-1, keepdims=True) - jnp.take_along_axis(lg, tokens, 1)


def _forward(arch, conf, root, seqs, control):
    """Residual streams after the last layer, one (S_pad, d) per sequence."""
    D = arch.dims(conf)
    o = jax.jit(lambda r: arch.outer_tensors(r, D))(root)
    xs = []
    for s in seqs:
        pad = -(-len(s) // BUCKET) * BUCKET
        ids = np.zeros((pad,), np.int32)
        ids[:len(s)] = s
        xs.append(f32(o["embed"][jnp.asarray(ids)]))
    del o
    return arch.forward_layers(conf, root, xs, seqs, control)


def _targets(x, s, n):
    """(S_pad,) served token each row predicts, and the rows that do:
    rows P-1 .. P+n-2 predict the served tokens 0..n-1."""
    P = len(s) - n
    t = np.zeros((x.shape[0],), np.int32)
    t[P - 1:P - 1 + n] = np.asarray(s[P:], np.int32)
    return t, slice(P - 1, P - 1 + n)


def teacher_forced(arch, conf: dict, seed: int, seqs, n_served, *,
                   control: bool = False):
    """Gaps of served tokens below the reference's best logit.

    ``arch``: the configuration's architecture module; ``seqs``: int
    arrays, prompt followed by the served tokens; ``n_served``: how many
    of each are served (the last ones). Returns
    ``{"gap": array}`` with one entry per served token, and with
    ``control`` also ``"control_gap"``: at the same positions, the gap of
    the token the fp8 forward ranks first."""
    if not seqs:
        return {"gap": np.zeros(0), "control_gap": np.zeros(0)}
    root = W.root_key(seed)
    D = arch.dims(conf)
    eps = float(conf["rms_norm_eps"])
    outer = jax.jit(lambda r: arch.outer_tensors(r, D))
    picks = None
    if control:
        xs = _forward(arch, conf, root, seqs, True)
        o = outer(root)
        picks = [np.asarray(_argmax(x, o["final_norm"], o["lm_head"], eps,
                                    True)) for x in xs]
        del xs, o
    xs = _forward(arch, conf, root, seqs, False)
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
