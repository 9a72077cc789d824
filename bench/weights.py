"""Seeded bf16 weights for a configuration, made on the device.

Each tensor slice has its own key, ``fold_in(root, tensor, layer,
expert)``, and its values use integer arithmetic up to one multiply and
one rounding to bf16. So the plain reference regenerates any one layer or
expert alone and gets bit for bit the values the program was served.
The draw is an Irwin-Hall sum of four random bytes (bell-shaped, bounded
at 3.45 standard deviations), scaled like the usual fan-in init:
``1/sqrt(fan_in)`` for projections, 0.02 for the embedding and router.
Norm scales lie in [0.875, 1.125].

The tree has the layout of ``repro.models.transformer.init_params``:
stacked layers, every leaf bf16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

TENSORS = ("embed", "lm_head", "final_norm", "ln1", "ln2", "wq", "wk", "wv",
           "wo", "router", "w_gate", "w_up", "w_down")
_IH_STD = math.sqrt(4 * (256 ** 2 - 1) / 12)      # std of a sum of 4 bytes


def dims(conf: dict) -> dict:
    """Sizes of a configuration file, under short names."""
    return {"d": conf["hidden_size"], "F": conf["intermediate_size"],
            "H": conf["num_attention_heads"],
            "K": conf["num_key_value_heads"], "hd": conf["head_dim"],
            "L": conf["num_hidden_layers"], "E": conf["num_local_experts"],
            "top_k": conf["num_experts_per_tok"], "V": conf["vocab_size"]}


def root_key(seed: int):
    """Key of a seed of up to 64 bits."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _key(root, name, layer=0, expert=0):
    k = jax.random.fold_in(root, TENSORS.index(name))
    return jax.random.fold_in(jax.random.fold_in(k, layer), expert)


def draw(root, name: str, shape, scale: float, layer=0, expert=0):
    """One tensor slice in bf16. ``scale`` None: a norm scale."""
    bits = jax.random.bits(_key(root, name, layer, expert), shape, jnp.uint32)
    if scale is None:
        b = (bits & 0xFF).astype(jnp.int32) - 128
        return (1.0 + b.astype(jnp.float32) / 1024.0).astype(jnp.bfloat16)
    s = sum(((bits >> (8 * i)) & 0xFF).astype(jnp.int32) for i in range(4))
    return ((s - 510).astype(jnp.float32)
            * np.float32(scale / _IH_STD)).astype(jnp.bfloat16)


def layer_tensors(root, D: dict, l):
    """Attention, norm and router weights of layer ``l`` (no experts)."""
    d, H, K, hd, E = D["d"], D["H"], D["K"], D["hd"], D["E"]
    return {
        "ln1": draw(root, "ln1", (d,), None, l),
        "ln2": draw(root, "ln2", (d,), None, l),
        "wq": draw(root, "wq", (d, H * hd), 1 / math.sqrt(d), l),
        "wk": draw(root, "wk", (d, K * hd), 1 / math.sqrt(d), l),
        "wv": draw(root, "wv", (d, K * hd), 1 / math.sqrt(d), l),
        "wo": draw(root, "wo", (H * hd, d), 1 / math.sqrt(H * hd), l),
        "router": draw(root, "router", (d, E), 0.02, l),
    }


def expert_tensors(root, D: dict, l, e):
    """SwiGLU weights of expert ``e`` of layer ``l``."""
    d, F = D["d"], D["F"]
    return {
        "w_gate": draw(root, "w_gate", (d, F), 1 / math.sqrt(d), l, e),
        "w_up": draw(root, "w_up", (d, F), 1 / math.sqrt(d), l, e),
        "w_down": draw(root, "w_down", (F, d), 1 / math.sqrt(F), l, e),
    }


def outer_tensors(root, D: dict):
    d, V = D["d"], D["V"]
    return {"embed": draw(root, "embed", (V, d), 0.02),
            "lm_head": draw(root, "lm_head", (d, V), 1 / math.sqrt(d)),
            "final_norm": draw(root, "final_norm", (d,), None)}


def _serving_tree(root, D: dict):
    """The program's parameter tree, one layer and one expert at a time."""
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


def serving_params(conf: dict, seed: int, mesh=None):
    """All weights in one jitted call, placed in the program's layout
    (``repro.sharding.param_specs``) on ``mesh``."""
    D = dims(conf)
    root = root_key(seed)
    build = jax.jit(lambda r: _serving_tree(r, D))
    if mesh is None:
        return build(root)
    from repro.sharding import make_shardings, param_specs
    shapes = jax.eval_shape(build, root)
    sh = make_shardings(mesh, param_specs(shapes, mesh=mesh))
    return jax.jit(lambda r: _serving_tree(r, D), out_shardings=sh)(root)
