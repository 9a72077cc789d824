"""Abstract (ShapeDtypeStruct) stand-ins for every model input/state.

Nothing here allocates device memory: params/opt/cache trees come from
``jax.eval_shape`` over the real init functions, then get NamedShardings
attached, so ``jit(...).lower(**specs)`` sees exactly what a real launch
would pass — the shannon/kernels dry-run pattern.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core.placement import identity_plan, stack_plans
from repro.models.transformer import Runtime, init_cache, init_model
from repro.optim.adamw import adamw_init
from repro.sharding import batch_axes, batch_axes_for, param_specs

# Sliding window applied to full-attention archs for long_500k decode
# (Mixtral's own 4K window — paper-faithful; DESIGN.md Sec 4).
LONG_CONTEXT_WINDOW = 4096


def _sds(tree_struct, spec_tree, mesh: Mesh):
    """Attach NamedShardings to an eval_shape output."""
    return jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                          sharding=NamedSharding(mesh, p)),
        tree_struct, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _cast_tree(struct, dtype):
    cast = lambda s: jax.ShapeDtypeStruct(
        s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype)
    return jax.tree.map(cast, struct)


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------

def runtime_for(cfg: ModelConfig, mesh: Mesh, shape: InputShape,
                *, use_kernel: bool = False,
                decode_expert_tp: bool = False) -> Runtime:
    window = 0
    if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid") \
            and not cfg.sliding_window:
        window = LONG_CONTEXT_WINDOW
    return Runtime(mesh=mesh, ep=cfg.is_moe, ep_ranks=mesh.shape["model"],
                   use_duplication=cfg.is_moe
                   and (cfg.moe.duplication_slots > 0),
                   use_kernel=use_kernel, window_override=window,
                   decode_expert_tp=decode_expert_tp)


def plan_args(cfg: ModelConfig, ep_ranks: int):
    """Concrete identity placement-plan stack (tiny arrays, replicated)."""
    if not cfg.is_moe:
        return None
    m = cfg.moe
    plans = [identity_plan(m.num_experts, ep_ranks, m.duplication_slots,
                           m.max_copies) for _ in range(cfg.num_moe_layers)]
    return stack_plans(plans)


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh,
                *, per_device_batch: Optional[int] = None) -> Dict:
    """ShapeDtypeStructs for the step inputs of (arch, input-shape).

    train/prefill: {"tokens", "labels"[, "prefix_embeds"|"frames"]}
    decode: {"tokens": (B, 1)} — the cache is separate (abstract_cache).
    """
    B, S = shape.global_batch, shape.seq_len
    b = batch_axes_for(mesh, B)
    bspec = NamedSharding(mesh, P(b, None))

    if shape.kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32,
                                               sharding=bspec)}

    specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=bspec)}
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32,
                                               sharding=bspec)
    if cfg.input_mode == "mixed" and cfg.num_prefix_embeddings:
        specs["prefix_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.num_prefix_embeddings, cfg.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(b, None, None)))
    if cfg.is_encdec:
        enc = cfg.encoder
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, enc.max_source_len, enc.d_model), jnp.bfloat16,
            sharding=NamedSharding(mesh, P(b, None, None)))
    return specs


# ---------------------------------------------------------------------------
# params / optimizer / cache
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig, mesh: Mesh, *, dtype=jnp.bfloat16,
                    fsdp: bool = True, expert_tp: bool = False):
    struct = jax.eval_shape(partial(init_model, cfg=cfg),
                            jax.random.PRNGKey(0))
    struct = _cast_tree(struct, dtype)
    fsdp_axes = batch_axes(mesh) if fsdp else ()
    fsdp_size = int(np.prod([mesh.shape[a] for a in fsdp_axes])) or 1
    specs = param_specs(struct, stacked_prefixes=("layers", "dense_layers",
                                                  "enc_layers"),
                        fsdp_axes=fsdp_axes, fsdp_size=fsdp_size, mesh=mesh,
                        expert_tp_axes=batch_axes(mesh) if expert_tp else ())
    return _sds(struct, specs, mesh), specs


def abstract_opt_state(params_struct, param_spec_tree, mesh: Mesh,
                       *, moment_dtype=jnp.float32):
    struct = jax.eval_shape(adamw_init, params_struct)
    # mu/nu inherit the param sharding; step is replicated
    from repro.optim.adamw import AdamWState
    mu = _sds(_cast_tree(struct.mu, moment_dtype), param_spec_tree, mesh)
    nu = _sds(_cast_tree(struct.nu, moment_dtype), param_spec_tree, mesh)
    step = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    return AdamWState(step=step, mu=mu, nu=nu)


def cache_specs(cfg: ModelConfig, cache_struct, mesh: Mesh, B: int):
    """PartitionSpec tree matching init_cache's structure."""
    m = mesh.shape["model"]
    b = batch_axes_for(mesh, B)     # batch axis dropped when not divisible

    def leaf_spec(path: str, leaf):
        nd = len(leaf.shape)
        if "cross_k" in path or "cross_v" in path:      # (L,B,Se,KV,hd)
            kv_ok = cfg.num_kv_heads % m == 0
            return P(None, b, None if kv_ok else "model",
                     "model" if kv_ok else None, None)
        if path.endswith("/k") or path.endswith("/v") or path in ("k", "v"):
            if nd == 5:                                  # (L,B,C,KV,hd)
                kv_ok = cfg.num_kv_heads % m == 0
                cl = leaf.shape[2]
                seq_ok = (not kv_ok) and cl % m == 0
                return P(None, b, "model" if seq_ok else None,
                         "model" if kv_ok else None, None)
            if nd == 4:                                  # hybrid: (B,W,KV,hd)
                kv_ok = cfg.num_kv_heads % m == 0
                return P(b, None, "model" if kv_ok else None, None)
        if "latent" in path:                             # MLA: (L,B,C,D)
            return P(None, b, None, None)
        if "wkv" in path:                                # rwkv: (L,B,H,hd,hd)
            h_ok = leaf.shape[2] % m == 0
            return P(None, b, "model" if h_ok else None, None, None)
        if "shift" in path:                              # rwkv: (L,B,d)
            return P(None, b, "model" if cfg.d_model % m == 0 else None)
        if path.endswith("/h") or path == "h":           # griffin: (B,dr)
            dr = leaf.shape[-1]
            return P(b, "model" if dr % m == 0 else None)
        if "conv" in path:                               # griffin: (B,w,dr)
            dr = leaf.shape[-1]
            return P(b, None, "model" if dr % m == 0 else None)
        # default: shard batch dim if it matches
        return P(*([b] + [None] * (nd - 1)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_struct)
    specs = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            if isinstance(k, jax.tree_util.DictKey):
                parts.append(str(k.key))
            elif isinstance(k, jax.tree_util.SequenceKey):
                parts.append(str(k.idx))
        specs.append(leaf_spec("/".join(parts), leaf))
    return jax.tree_util.tree_unflatten(treedef, specs)


def abstract_cache(cfg: ModelConfig, rt: Runtime, shape: InputShape,
                   mesh: Mesh):
    B = shape.global_batch
    max_len = shape.seq_len
    if cfg.input_mode == "mixed" and cfg.num_prefix_embeddings:
        max_len += cfg.num_prefix_embeddings    # prefix fills cache positions
    struct = jax.eval_shape(
        partial(init_cache, cfg, rt, B, max_len))
    specs = cache_specs(cfg, struct, mesh, B)
    return _sds(struct, specs, mesh)
