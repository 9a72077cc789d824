"""DeepSeek-V2 (``model_type`` "deepseek_v2"): what the benchmark knows of
this architecture alone.

A decoder of RMSNorm, multi-head latent attention (MLA, no q LoRA: the
query straight from the residual stream; ``kv_a_proj_with_mqa`` to a
latent, RMSNorm'd, and one shared rotary key; ``kv_b_proj`` expanding the
latent into per-head keys and values), YaRN rotary frequencies and
softmax scale, ``first_k_dense_replace`` leading layers with a dense
SwiGLU MLP, then layers of a softmax router over routed SwiGLU experts
(greedy top-k, gates renormalised only where ``norm_topk_prob``, a
``routed_scaling_factor`` of 1) beside ``n_shared_experts`` shared experts
(one MLP of that many times the expert width), and an untied LM head.

Layer ``l`` of the configuration is layer ``l`` of the weights' keys:
the leading dense layers first, then the MoE layers. The program holds
the dense layers' weights under ``dense_layers`` and the others under
``layers`` (``repro.models.transformer.init_params``).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

import counts as C
import reference as Ref
import weights as W

SOURCE = "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json"

# published widths of each source a configuration file may name; a file
# holds them unless it lists the key in its ``reduced``
PUBLISHED = {
    SOURCE: {
        "hidden_size": 2048, "intermediate_size": 10944,
        "moe_intermediate_size": 1408, "num_attention_heads": 16,
        "num_key_value_heads": 16, "kv_lora_rank": 512, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "n_routed_experts": 64, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "first_k_dense_replace": 1,
        "moe_layer_freq": 1, "norm_topk_prob": False,
        "routed_scaling_factor": 1, "scoring_func": "softmax",
        "topk_method": "greedy", "n_group": 1, "topk_group": 1,
        "num_hidden_layers": 27, "vocab_size": 102400, "rms_norm_eps": 1e-06,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}},
}

# a tensor's index here seeds its key: append, never reorder
TENSORS = ("embed", "lm_head", "final_norm", "ln1", "ln2", "w_q", "w_kv_a",
           "kv_norm", "w_uk", "w_uv", "wo", "router", "w_gate", "w_up",
           "w_down", "shared_gate", "shared_up", "shared_down", "dense_gate",
           "dense_up", "dense_down")


def dims(conf: dict) -> dict:
    """Sizes of a configuration file, under short names."""
    if conf["q_lora_rank"] or conf["scoring_func"] != "softmax" \
            or conf["topk_method"] != "greedy" or conf["moe_layer_freq"] != 1 \
            or conf["routed_scaling_factor"] != 1:
        raise SystemExit(f"{conf['name']}: the program serves DeepSeek-V2 "
                         "with no q LoRA, a softmax greedy router with "
                         "unscaled gates and experts in every layer after "
                         "the dense ones")
    y = conf["rope_scaling"]
    return {"d": conf["hidden_size"], "H": conf["num_attention_heads"],
            "r": conf["kv_lora_rank"], "nope": conf["qk_nope_head_dim"],
            "rope": conf["qk_rope_head_dim"], "vd": conf["v_head_dim"],
            "L": conf["num_hidden_layers"],
            "k_dense": conf["first_k_dense_replace"],
            "F_dense": conf["intermediate_size"],
            "F": conf["moe_intermediate_size"],
            "E": conf["n_routed_experts"], "top_k": conf["num_experts_per_tok"],
            "shared": conf["n_shared_experts"], "V": conf["vocab_size"],
            "norm_topk": bool(conf["norm_topk_prob"]),
            "theta": float(conf["rope_theta"]),
            "yarn": (float(y["factor"]),
                     int(y["original_max_position_embeddings"]),
                     float(y["beta_fast"]), float(y["beta_slow"]),
                     float(y["mscale"]), float(y["mscale_all_dim"]))}


def model_config(conf: dict, engine: dict):
    """The program's ``ModelConfig``; ``engine`` is the file's ``engine``."""
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    YarnConfig)
    D = dims(conf)
    factor, orig, fast, slow, ms, ms_all = D["yarn"]
    return ModelConfig(
        name=conf["name"], family="moe", num_layers=D["L"],
        d_model=D["d"], num_heads=D["H"], num_kv_heads=D["H"],
        head_dim=D["nope"] + D["rope"], d_ff=D["F_dense"], vocab_size=D["V"],
        attention="mla", rope_theta=D["theta"],
        yarn=YarnConfig(factor=factor, original_max_position=orig,
                        beta_fast=fast, beta_slow=slow, mscale=ms,
                        mscale_all_dim=ms_all),
        norm="rmsnorm", activation="swiglu",
        tie_embeddings=conf["tie_word_embeddings"],
        first_k_dense=D["k_dense"],
        mla=MLAConfig(kv_lora_rank=D["r"], q_lora_rank=0,
                      rope_head_dim=D["rope"], nope_head_dim=D["nope"],
                      v_head_dim=D["vd"]),
        moe=MoEConfig(num_experts=D["E"], top_k=D["top_k"], d_ff_expert=D["F"],
                      num_shared_experts=D["shared"],
                      norm_topk_prob=D["norm_topk"],
                      capacity_factor=float(engine["capacity_factor"]),
                      max_copies=engine["max_copies"]),
        source=conf["source"])


# --------------------------------------------------------------- weights

def attn_tensors(root, D: dict, l):
    """Norms and attention weights of layer ``l``; ``kv_b_proj`` is drawn
    as its key half ``w_uk`` and value half ``w_uv``."""
    d, H, r = D["d"], D["H"], D["r"]
    qk = D["nope"] + D["rope"]
    return {
        "ln1": W.draw(root, TENSORS, "ln1", (d,), None, l),
        "ln2": W.draw(root, TENSORS, "ln2", (d,), None, l),
        "w_q": W.draw(root, TENSORS, "w_q", (d, H * qk), 1 / math.sqrt(d), l),
        "w_kv_a": W.draw(root, TENSORS, "w_kv_a", (d, r + D["rope"]),
                         1 / math.sqrt(d), l),
        "kv_norm": W.draw(root, TENSORS, "kv_norm", (r,), None, l),
        "w_uk": W.draw(root, TENSORS, "w_uk", (r, H * D["nope"]),
                       1 / math.sqrt(r), l),
        "w_uv": W.draw(root, TENSORS, "w_uv", (r, H * D["vd"]),
                       1 / math.sqrt(r), l),
        "wo": W.draw(root, TENSORS, "wo", (H * D["vd"], d),
                     1 / math.sqrt(H * D["vd"]), l),
    }


def _mlp(root, prefix, d, F, l, e=0):
    return {
        "w_gate": W.draw(root, TENSORS, prefix + "gate", (d, F),
                         1 / math.sqrt(d), l, e),
        "w_up": W.draw(root, TENSORS, prefix + "up", (d, F), 1 / math.sqrt(d),
                       l, e),
        "w_down": W.draw(root, TENSORS, prefix + "down", (F, d),
                         1 / math.sqrt(F), l, e),
    }


def dense_tensors(root, D: dict, l):
    """The dense MLP of a leading layer ``l``."""
    return _mlp(root, "dense_", D["d"], D["F_dense"], l)


def moe_tensors(root, D: dict, l):
    """Router and shared experts of MoE layer ``l`` (no routed experts)."""
    return {"router": W.draw(root, TENSORS, "router", (D["d"], D["E"]), 0.02,
                             l),
            "shared": _mlp(root, "shared_", D["d"], D["shared"] * D["F"], l)}


def expert_tensors(root, D: dict, l, e):
    """SwiGLU weights of routed expert ``e`` of layer ``l``."""
    return _mlp(root, "w_", D["d"], D["F"], l, e)


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
    def attn_block(l):
        t = attn_tensors(root, D, l)
        return {"ln1": {"scale": t["ln1"]}, "ln2": {"scale": t["ln2"]},
                "attn": {"w_q": {"w": t["w_q"]}, "w_kv_a": {"w": t["w_kv_a"]},
                         "kv_norm": {"scale": t["kv_norm"]},
                         "w_uk": {"w": t["w_uk"]}, "w_uv": {"w": t["w_uv"]},
                         "wo": {"w": t["wo"]}}}

    def dense_layer(l):
        return {**attn_block(l), "ffn": dense_tensors(root, D, l)}

    def moe_layer(l):
        m = moe_tensors(root, D, l)
        ex = jax.lax.map(lambda e: expert_tensors(root, D, l, e),
                         jnp.arange(D["E"]))
        return {**attn_block(l),
                "moe": {"router": {"w": m["router"]}, "experts": ex,
                        "shared": m["shared"]}}

    o = outer_tensors(root, D)
    tree = {"embed": {"table": o["embed"]},
            "final_norm": {"scale": o["final_norm"]},
            "lm_head": {"w": o["lm_head"]},
            "layers": jax.lax.map(moe_layer, jnp.arange(D["k_dense"], D["L"]))}
    if D["k_dense"]:
        tree["dense_layers"] = jax.lax.map(dense_layer,
                                           jnp.arange(D["k_dense"]))
    return tree


# ------------------------------------------------------------- reference

def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(D):
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies over the
    rotary dims: pairs that turn more than ``beta_fast`` times over the
    original context keep their frequency, those under ``beta_slow`` are
    slowed by ``factor``, with a linear ramp between."""
    dim, base = D["rope"], D["theta"]
    factor, orig, fast, slow, _, _ = D["yarn"]

    def corr_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(fast)), 0)
    high = min(math.ceil(corr_dim(slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rope_yarn(x, D):
    """x: (S, n, rope); rotate-half pairing at positions 0..S-1, YaRN
    frequencies, cos and sin scaled by mscale(mscale) / mscale(all)."""
    factor, _, _, _, ms, ms_all = D["yarn"]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * yarn_inv_freq(D)[None, :]
    k = yarn_mscale(factor, ms) / yarn_mscale(factor, ms_all)
    cos, sin = k * jnp.cos(ang)[:, None], k * jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("D", "eps", "control"))
def _attention(x, n_real, t, D, eps, control):
    """x: (S, d) residual stream of one sequence (rows >= n_real padding):
    MLA in the expanded form."""
    D = dict(D)
    q8 = partial(Ref.maybe_q, control=control)
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        H, r, nope, rope, vd = D["H"], D["r"], D["nope"], D["rope"], D["vd"]
        h = Ref.rms(x, Ref.f32(t["ln1"]), eps)
        q = (h @ q8(Ref.f32(t["w_q"]))).reshape(S, H, nope + rope)
        kv = h @ q8(Ref.f32(t["w_kv_a"]))
        c = Ref.rms(kv[:, :r], Ref.f32(t["kv_norm"]), eps)
        k_pe = rope_yarn(kv[:, None, r:], D)                  # (S, 1, rope)
        k_nope = (c @ q8(Ref.f32(t["w_uk"]))).reshape(S, H, nope)
        v = (c @ q8(Ref.f32(t["w_uv"]))).reshape(S, H, vd)
        q = jnp.concatenate([q[..., :nope], rope_yarn(q[..., nope:], D)], -1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (S, H, rope))],
                            -1)
        scale = (nope + rope) ** -0.5 * yarn_mscale(D["yarn"][0],
                                                    D["yarn"][5]) ** 2
        s = jnp.einsum("qhd,khd->hqk", q, k) * scale
        pos = jnp.arange(S)
        mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_real)
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
        return x + o.reshape(S, H * vd) @ q8(Ref.f32(t["wo"]))


def _swiglu(h, w, control):
    g = h @ Ref.maybe_q(Ref.f32(w["w_gate"]), control)
    u = h @ Ref.maybe_q(Ref.f32(w["w_up"]), control)
    return (jax.nn.silu(g) * u) @ Ref.maybe_q(Ref.f32(w["w_down"]), control)


@partial(jax.jit, static_argnames=("D", "eps", "control"))
def _dense_ffn(root, x, l, D, eps, control):
    """x + the dense MLP of leading layer ``l``, over the rows of every
    sequence at once (the FFN is row-wise)."""
    D = dict(D)
    with jax.default_matmul_precision("highest"):
        h = Ref.rms(x, Ref.f32(attn_tensors(root, D, l)["ln2"]), eps)
        return x + _swiglu(h, dense_tensors(root, D, l), control)


@partial(jax.jit, static_argnames=("D", "eps", "control"))
def _moe_ffn(root, x, l, D, eps, control):
    """x + MoE layer ``l``'s shared experts and each routed expert weighted
    by its gate (zero outside a row's top k), over the rows of every
    sequence at once; the routed experts drawn one at a time."""
    D = dict(D)
    with jax.default_matmul_precision("highest"):
        h = Ref.rms(x, Ref.f32(attn_tensors(root, D, l)["ln2"]), eps)
        m = moe_tensors(root, D, l)
        probs = jax.nn.softmax(h @ Ref.f32(m["router"]), axis=-1)
        top, idx = jax.lax.top_k(probs, D["top_k"])
        if D["norm_topk"]:
            top = top / top.sum(-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(h.shape[0])[:, None], idx].set(top)
        y = x + _swiglu(h, m["shared"], control)

        def expert(e, y):
            g = jax.lax.dynamic_index_in_dim(gates, e, 1, keepdims=False)
            return y + g[:, None] * _swiglu(h, expert_tensors(root, D, l, e),
                                            control)
        return jax.lax.fori_loop(0, D["E"], expert, y)


ROWS = 2048      # the FFN's rows are padded to a multiple of this


def forward_layers(conf, root, xs, seqs, control):
    """The float32 layers over embedded sequences ``xs`` (one (S_pad, d)
    each, ``seqs`` their tokens): the residual streams after the last
    layer. Weights are drawn a layer and an expert at a time."""
    D = dims(conf)
    Dh = tuple(sorted(D.items()))
    eps = float(conf["rms_norm_eps"])
    attn = jax.jit(lambda r, l: attn_tensors(r, D, l))
    ends = [0]
    for x in xs:
        ends.append(ends[-1] + x.shape[0])
    pad = -ends[-1] % ROWS
    for l in range(D["L"]):
        t = attn(root, l)
        xs = [_attention(x, len(s), t, Dh, eps, control)
              for x, s in zip(xs, seqs)]
        del t
        ffn = _dense_ffn if l < D["k_dense"] else _moe_ffn
        y = ffn(root, jnp.pad(jnp.concatenate(xs), ((0, pad), (0, 0))), l,
                Dh, eps, control)
        xs = [y[a:b] for a, b in zip(ends[:-1], ends[1:])]
        del y
    return xs


# ---------------------------------------------------------------- counts
# Useful work only (see ``counts.py``); ``D`` is ``dims(conf)``.

def expert_bytes(D) -> int:
    """One routed expert's SwiGLU weights (gate, up, down)."""
    return 3 * D["d"] * D["F"] * C.BF16


def attn_weight_bytes(D) -> int:
    """One layer's MLA weights: q, kv_a, the latent norm, kv_b (key and
    value halves), output."""
    d, H, r = D["d"], D["H"], D["r"]
    return (d * H * (D["nope"] + D["rope"]) + d * (r + D["rope"]) + r
            + r * H * (D["nope"] + D["vd"]) + H * D["vd"] * d) * C.BF16


def moe_layer_fixed_bytes(D) -> int:
    """One MoE layer's weights outside its routed experts: attention,
    norms, router, shared experts."""
    d = D["d"]
    return (attn_weight_bytes(D) + (2 * d + d * D["E"]
                                    + 3 * d * D["shared"] * D["F"]) * C.BF16)


def dense_layer_bytes(D) -> int:
    """One leading dense layer: attention, norms, its MLP."""
    d = D["d"]
    return attn_weight_bytes(D) + (2 * d + 3 * d * D["F_dense"]) * C.BF16


def fixed_bytes(D) -> int:
    """What a decode reads whatever its routing: the dense layers, every
    MoE layer's weights outside its routed experts, and the head."""
    k = D["k_dense"]
    return (k * dense_layer_bytes(D) + (D["L"] - k) * moe_layer_fixed_bytes(D)
            + C.head_bytes(D))


def kv_bytes_per_token(D) -> int:
    """One latent row (normed latent and roped key) in every layer."""
    return (D["r"] + D["rope"]) * C.BF16 * D["L"]


def kv_read_bytes(D, context: int, block: int) -> int:
    """Latent rows a paged decode must read for one sequence: its live
    blocks."""
    return C.paged_positions(context, block) * kv_bytes_per_token(D)


def _ffn_flops(D, dense: bool) -> int:
    """Per token: a dense layer's MLP, or a MoE layer's router, top-k
    routed experts and shared experts."""
    d = D["d"]
    if dense:
        return 3 * 2 * d * D["F_dense"]
    return (2 * d * D["E"] + D["top_k"] * 3 * 2 * d * D["F"]
            + 3 * 2 * d * D["shared"] * D["F"])


def _layers_flops(D, per_layer_attn: int) -> int:
    k = D["k_dense"]
    return (D["L"] * per_layer_attn + k * _ffn_flops(D, True)
            + (D["L"] - k) * _ffn_flops(D, False))


def prefill_flops(D, prompt: int) -> int:
    """One prompt, expanded MLA: every real token through the projections
    (q, kv_a, kv_b, output) and FFNs, causal attention (QK over the
    nope + rope dims, PV over v), the LM head at the last position."""
    d, H, r = D["d"], D["H"], D["r"]
    qk = D["nope"] + D["rope"]
    proj = 2 * (d * H * qk + d * (r + D["rope"]) + r * H * (D["nope"] + D["vd"])
                + H * D["vd"] * d)
    attn = D["L"] * 2 * H * (qk + D["vd"]) * C.causal_pairs(prompt)
    return prompt * _layers_flops(D, proj) + attn + C.head_flops(D)


def decode_flops(D, context: int) -> int:
    """One decoded token, absorbed MLA, attending over ``context``
    positions (itself included): projections (q, kv_a, the two absorbs,
    output), scores over the latent row and the rope key, the weighted
    latent; FFNs; the LM head."""
    d, H, r = D["d"], D["H"], D["r"]
    proj = 2 * (d * H * (D["nope"] + D["rope"]) + d * (r + D["rope"])
                + H * D["nope"] * r + H * r * D["vd"] + H * D["vd"] * d)
    attn = 2 * H * (2 * r + D["rope"]) * context
    return _layers_flops(D, proj + attn) + C.head_flops(D)
