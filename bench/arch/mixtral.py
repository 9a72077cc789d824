"""Mixtral (``model_type`` "mixtral"): what the benchmark knows of this
architecture alone.

A decoder of RMSNorm, rotary GQA, a softmax router whose top-k gates are
renormalised, SwiGLU experts and an untied LM head. This module gives
the harness (``run.load_arch``) the architecture's sizes, the program's
``ModelConfig``, the seeded serving tree (drawn by ``weights.draw``), the
plain reference's layers (``reference.teacher_forced`` drives them) and
the operations and bytes a step requires (read by ``bench/metrics``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

import counts as C
import reference as Ref
import weights as W

# published widths of each source a configuration file may name; a file
# holds them unless it lists the key in its ``reduced``
PUBLISHED = {
    "https://huggingface.co/mistralai/Mixtral-8x7B-v0.1": {
        "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
        "num_local_experts": 8, "num_experts_per_tok": 2,
        "vocab_size": 32000},
}

# a tensor's index here seeds its key: append, never reorder
TENSORS = ("embed", "lm_head", "final_norm", "ln1", "ln2", "wq", "wk", "wv",
           "wo", "router", "w_gate", "w_up", "w_down")


def dims(conf: dict) -> dict:
    """Sizes of a configuration file, under short names."""
    return {"d": conf["hidden_size"], "F": conf["intermediate_size"],
            "H": conf["num_attention_heads"],
            "K": conf["num_key_value_heads"], "hd": conf["head_dim"],
            "L": conf["num_hidden_layers"], "E": conf["num_local_experts"],
            "top_k": conf["num_experts_per_tok"], "V": conf["vocab_size"]}


def model_config(conf: dict, engine: dict):
    """The program's ``ModelConfig``; ``engine`` is the file's ``engine``."""
    from repro.configs.base import ModelConfig, MoEConfig
    return ModelConfig(
        name=conf["name"], family="moe",
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attention="gqa", sliding_window=conf["sliding_window"] or 0,
        rope_theta=float(conf["rope_theta"]), norm="rmsnorm",
        activation="swiglu", tie_embeddings=conf["tie_word_embeddings"],
        moe=MoEConfig(num_experts=conf["num_local_experts"],
                      top_k=conf["num_experts_per_tok"],
                      d_ff_expert=conf["intermediate_size"],
                      capacity_factor=float(engine["capacity_factor"]),
                      max_copies=engine["max_copies"]),
        source=conf["source"])


# --------------------------------------------------------------- weights

def layer_tensors(root, D: dict, l):
    """Attention, norm and router weights of layer ``l`` (no experts)."""
    d, H, K, hd, E = D["d"], D["H"], D["K"], D["hd"], D["E"]
    return {
        "ln1": W.draw(root, TENSORS, "ln1", (d,), None, l),
        "ln2": W.draw(root, TENSORS, "ln2", (d,), None, l),
        "wq": W.draw(root, TENSORS, "wq", (d, H * hd), 1 / math.sqrt(d), l),
        "wk": W.draw(root, TENSORS, "wk", (d, K * hd), 1 / math.sqrt(d), l),
        "wv": W.draw(root, TENSORS, "wv", (d, K * hd), 1 / math.sqrt(d), l),
        "wo": W.draw(root, TENSORS, "wo", (H * hd, d),
                     1 / math.sqrt(H * hd), l),
        "router": W.draw(root, TENSORS, "router", (d, E), 0.02, l),
    }


def expert_tensors(root, D: dict, l, e):
    """SwiGLU weights of expert ``e`` of layer ``l``."""
    d, F = D["d"], D["F"]
    return {
        "w_gate": W.draw(root, TENSORS, "w_gate", (d, F), 1 / math.sqrt(d),
                         l, e),
        "w_up": W.draw(root, TENSORS, "w_up", (d, F), 1 / math.sqrt(d), l, e),
        "w_down": W.draw(root, TENSORS, "w_down", (F, d), 1 / math.sqrt(F),
                         l, e),
    }


def outer_tensors(root, D: dict):
    """Embedding (V, d), LM head (d, V) and final norm scale."""
    d, V = D["d"], D["V"]
    return {"embed": W.draw(root, TENSORS, "embed", (V, d), 0.02),
            "lm_head": W.draw(root, TENSORS, "lm_head", (d, V),
                              1 / math.sqrt(d)),
            "final_norm": W.draw(root, TENSORS, "final_norm", (d,), None)}


def serving_tree(root, D: dict):
    """The program's parameter tree (the layout of
    ``repro.models.transformer.init_params``: stacked layers, every leaf
    bf16), one layer and one expert at a time."""
    def layer(l):
        t = layer_tensors(root, D, l)
        ex = jax.lax.map(lambda e: expert_tensors(root, D, l, e),
                         jnp.arange(D["E"]))
        return {"ln1": {"scale": t["ln1"]}, "ln2": {"scale": t["ln2"]},
                "attn": {n: {"w": t[n]} for n in ("wq", "wk", "wv", "wo")},
                "moe": {"router": {"w": t["router"]}, "experts": ex}}

    o = outer_tensors(root, D)
    return {"embed": {"table": o["embed"]},
            "final_norm": {"scale": o["final_norm"]},
            "lm_head": {"w": o["lm_head"]},
            "layers": jax.lax.map(layer, jnp.arange(D["L"]))}


# ------------------------------------------------------------- reference

@partial(jax.jit, static_argnames=("D", "eps", "theta", "control"))
def _attention(x, n_real, t, D, eps, theta, control):
    """x: (S, d) residual stream of one sequence (rows >= n_real padding)."""
    D = dict(D)
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        H, K, hd = D["H"], D["K"], D["hd"]
        h = Ref.rms(x, Ref.f32(t["ln1"]), eps)
        q = (h @ Ref.maybe_q(Ref.f32(t["wq"]), control)).reshape(S, H, hd)
        k = (h @ Ref.maybe_q(Ref.f32(t["wk"]), control)).reshape(S, K, hd)
        v = (h @ Ref.maybe_q(Ref.f32(t["wv"]), control)).reshape(S, K, hd)
        q, k = Ref.rope(q, theta), Ref.rope(k, theta)
        G = H // K
        k = jnp.repeat(k, G, axis=1)                # head h reads kv h // G
        v = jnp.repeat(v, G, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        pos = jnp.arange(S)
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_real)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
        return x + o @ Ref.maybe_q(Ref.f32(t["wo"]), control)


@partial(jax.jit, static_argnames=("D", "eps"))
def _route(x, t, D, eps):
    """Normed input of the expert layer and each token's gate per expert
    (zero for experts outside its top k)."""
    D = dict(D)
    with jax.default_matmul_precision("highest"):
        h = Ref.rms(x, Ref.f32(t["ln2"]), eps)
        probs = jax.nn.softmax(h @ Ref.f32(t["router"]), axis=-1)
        top, idx = jax.lax.top_k(probs, D["top_k"])
        top = top / top.sum(-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(h.shape[0])[:, None], idx].set(top)
        return h, gates


@partial(jax.jit, static_argnames=("control",))
def _expert(y, h, gate, ew, control):
    with jax.default_matmul_precision("highest"):
        g = h @ Ref.maybe_q(Ref.f32(ew["w_gate"]), control)
        u = h @ Ref.maybe_q(Ref.f32(ew["w_up"]), control)
        return y + gate[:, None] * (
            (jax.nn.silu(g) * u) @ Ref.maybe_q(Ref.f32(ew["w_down"]), control))


def forward_layers(conf, root, xs, seqs, control):
    """The float32 layers over embedded sequences ``xs`` (one (S_pad, d)
    each, ``seqs`` their tokens): the residual streams after the last
    layer. Weights are drawn a layer and an expert at a time."""
    D = dims(conf)
    Dh = tuple(sorted(D.items()))
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    layer = jax.jit(lambda r, l: layer_tensors(r, D, l))
    expert = jax.jit(lambda r, l, e: expert_tensors(r, D, l, e))
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


# ---------------------------------------------------------------- counts
# Useful work only (see ``counts.py``); ``D`` is ``dims(conf)``.

def expert_bytes(D) -> int:
    """One expert's SwiGLU weights (gate, up, down)."""
    return 3 * D["d"] * D["F"] * C.BF16


def attn_weight_bytes(D) -> int:
    """One layer's q, k, v and output projections."""
    d, H, K, hd = D["d"], D["H"], D["K"], D["hd"]
    return (2 * d * H * hd + 2 * d * K * hd) * C.BF16


def layer_dense_bytes(D) -> int:
    """One layer's weights outside its experts: attention, router, norms."""
    return attn_weight_bytes(D) + (D["d"] * D["E"] + 2 * D["d"]) * C.BF16


def fixed_bytes(D) -> int:
    """What a decode reads whatever its routing: every held layer's
    weights outside its experts, and the head."""
    return D["L"] * layer_dense_bytes(D) + C.head_bytes(D)


def kv_bytes_per_token(D) -> int:
    """K and V of one position in every layer."""
    return 2 * D["K"] * D["hd"] * C.BF16 * D["L"]


def kv_read_bytes(D, context: int, block: int) -> int:
    """KV a paged decode must read for one sequence: its live blocks."""
    return C.paged_positions(context, block) * kv_bytes_per_token(D)


def body_flops(D) -> int:
    """Per token and over all layers, everything but attention scores:
    projections, router and the top-k experts."""
    d, H, K, hd, F = D["d"], D["H"], D["K"], D["hd"], D["F"]
    proj = 2 * (2 * d * H * hd + 2 * d * K * hd)
    router = 2 * d * D["E"]
    experts = D["top_k"] * 3 * 2 * d * F
    return D["L"] * (proj + router + experts)


def token_flops(D) -> int:
    """A decoded token, but for attention scores: body and LM head."""
    return body_flops(D) + C.head_flops(D)


def attn_flops(D, attended: int) -> int:
    """QK and PV products over ``attended`` (query, key) pairs per layer."""
    return D["L"] * 2 * 2 * D["H"] * D["hd"] * attended


def prefill_flops(D, prompt: int) -> int:
    """One prompt: every real token through the body, causal attention,
    the LM head at the last position only."""
    return (prompt * body_flops(D) + C.head_flops(D)
            + attn_flops(D, C.causal_pairs(prompt)))


def decode_flops(D, context: int) -> int:
    """One decoded token attending over ``context`` positions (itself
    included)."""
    return token_flops(D) + attn_flops(D, context)
