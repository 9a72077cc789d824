"""Sharding rules: parameter-path -> PartitionSpec mapping.

Mesh axes: ("pod", "data", "model") multi-pod or ("data", "model")
single-pod. Batch is sharded over (pod, data); the "model" axis carries
tensor parallelism for attention/FFN/vocab and expert parallelism for MoE.

Conventions (dims refer to the *unstacked* parameter; scanned layer stacks
prepend an unsharded L dim which is handled automatically):

  embedding table (V, d)        -> (model, None)        vocab-sharded
  attention wq/wk/wv (d, H*hd)  -> (None, model)        head-sharded
  attention wo (H*hd, d)        -> (model, None)
  dense ffn w_gate/w_up (d, f)  -> (None, model)
  dense ffn w_down (f, d)       -> (model, None)
  moe experts (E, d, f)         -> (model, None, None)  expert-parallel
  router, norms, biases, small  -> replicated
"""

from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# (regex over '/'-joined path, spec for the LAST ndim dims of the leaf)
_RULES = [
    (r"embed/table$", ("model", None)),
    (r"(wq|wk|wv|w_q)/w$", (None, "model")),
    (r"(wo|w_o)/w$", ("model", None)),
    (r"(w_uk|w_uv)/w$", (None, "model")),          # MLA up-projections: head-sharded
    (r"w_kv_a/w$", (None, None)),                   # MLA latent down-projection
    (r"experts/w_gate$", ("model", None, None)),
    (r"experts/w_up$", ("model", None, None)),
    (r"experts/w_down$", ("model", None, None)),
    (r"(ffn|shared|dense|channel_mix)/w_(gate|up|k)/w$", (None, "model")),
    (r"(ffn|shared|dense|channel_mix)/w_(down|v)/w$", ("model", None)),
    (r"(shared|dense)/w_(gate|up)$", (None, "model")),
    (r"(shared|dense)/w_down$", ("model", None)),
    # rwkv time-mix projections
    (r"time_mix/w_(r|k|v|g)/w$", (None, "model")),
    (r"time_mix/w_o/w$", ("model", None)),
    # griffin recurrent block
    (r"(w_gate|w_main)/w$", (None, "model")),
    (r"w_out/w$", ("model", None)),
    (r"(w_a|w_x)/w$", ("model", "model_diag")),    # placeholder; replaced below
]

# RG-LRU per-channel maps (dr -> dr) stay model-sharded on output only.
_RULES = [(p, s) for p, s in _RULES if s != ("model", "model_diag")]
_RULES.append((r"(w_a|w_x)/w$", (None, "model")))


def spec_for_path(path: str, ndim: int, stacked: bool) -> P:
    """PartitionSpec for a parameter. ``stacked``: leading scan-layer dim."""
    body_ndim = ndim - (1 if stacked else 0)
    for pat, spec in _RULES:
        if re.search(pat, path):
            spec = tuple(spec)
            if len(spec) < body_ndim:            # e.g. biases under matched scope
                spec = (None,) * (body_ndim - len(spec)) + spec
            if len(spec) != body_ndim:
                break
            full = ((None,) if stacked else ()) + spec
            return P(*full)
    return P()                                    # replicated


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, leaf in flat:
        parts = []
        for k in kp:
            if isinstance(k, jax.tree_util.DictKey):
                parts.append(str(k.key))
            elif isinstance(k, jax.tree_util.SequenceKey):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        out.append(("/".join(parts), leaf))
    return out, treedef


def _add_fsdp(spec: P, shape, fsdp_axes, fsdp_size: int,
              stacked: bool) -> P:
    """ZeRO-style extension: shard the largest still-unsharded dim of a
    >=2D weight over the batch axes, when evenly divisible. Parameters are
    then stored fully sharded and all-gathered at use (XLA inserts the
    gathers); this is the standard MaxText-style fsdp axis. The scanned
    layer dim (leading dim of stacked params) is never fsdp-sharded."""
    if not fsdp_axes or len(shape) < 2:
        return spec
    used = set()
    for s in spec:
        for a in (s if isinstance(s, tuple) else (s,)):
            if a:
                used.add(a)
    if used & set(fsdp_axes):            # axis already carried by the spec
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    first = 1 if stacked else 0
    free = [(d, i) for i, (d, s) in enumerate(zip(shape, parts))
            if i >= first and s is None and d % fsdp_size == 0
            and d >= fsdp_size]
    if not free:
        return spec
    _, idx = max(free)
    parts[idx] = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
    return P(*parts)


def _sanitize(spec: P, shape, mesh: Optional[Mesh]) -> P:
    """Drop mesh axes from dims they don't evenly divide (e.g. odd vocab
    sizes like minicpm's 122753 can't be sharded 16-way)."""
    if mesh is None:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for d, s in zip(shape, parts):
        if s is None:
            out.append(None)
            continue
        axes = s if isinstance(s, tuple) else (s,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        out.append(s if d % n == 0 else None)
    return P(*out)


_EXPERT_TP_RULES = [
    (r"experts/w_gate$", ("model", None, "TP")),
    (r"experts/w_up$", ("model", None, "TP")),
    (r"experts/w_down$", ("model", "TP", None)),
]


def param_specs(params, stacked_prefixes=("layers", "dense_layers",
                                          "enc_layers", "dec_layers"),
                fsdp_axes=(), fsdp_size: int = 1, mesh: Optional[Mesh] = None,
                expert_tp_axes=()):
    """PartitionSpec pytree matching ``params``. Leaves under a stacked
    prefix are treated as having a leading layer dim. ``fsdp_axes``: also
    shard weights over these batch axes (ZeRO-3 storage). ``mesh``: when
    given, axes are dropped from dims they don't evenly divide.
    ``expert_tp_axes``: resident 2D expert layout (EP x f-TP, for decode)."""
    flat, treedef = _flatten_with_paths(params)
    specs = []
    for path, leaf in flat:
        stacked = any(path.startswith(p + "/") or ("/" + p + "/") in path
                      for p in stacked_prefixes)
        spec = spec_for_path(path, np.ndim(leaf), stacked)
        if expert_tp_axes:
            for pat, tpl in _EXPERT_TP_RULES:
                if re.search(pat, path):
                    body = tuple(expert_tp_axes if s == "TP" else s
                                 for s in tpl)
                    spec = P(*(((None,) if stacked else ()) + body))
                    break
        spec = _sanitize(spec, np.shape(leaf), mesh)
        spec = _add_fsdp(spec, np.shape(leaf), tuple(fsdp_axes), fsdp_size,
                         stacked)
        spec = _sanitize(spec, np.shape(leaf), mesh)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def make_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def batch_axes(mesh: Mesh):
    """Mesh axis names that shard the batch dimension."""
    names = mesh.axis_names
    return tuple(n for n in names if n in ("pod", "data"))


def batch_axes_for(mesh: Mesh, B: int):
    """Batch axes, dropped to replication when B isn't evenly divisible
    (e.g. long-context decode with global_batch=1)."""
    b = batch_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in b])) if b else 1
    return b if b and B % n == 0 else ()


def kv_head_axis(mesh: Mesh, num_kv_heads: int):
    """Mesh axis the KV-head dim of caches and paged-attention operands is
    split over: "model", like the attention projections, when it divides
    the KV heads evenly; None (replicated) otherwise."""
    return "model" if num_kv_heads % mesh.shape["model"] == 0 else None


def paged_pool_spec(mesh: Mesh, num_kv_heads: int, stacked: bool) -> P:
    """(L?, N_blocks, block, K, hd) paged KV pool: head-split, blocks whole."""
    h = kv_head_axis(mesh, num_kv_heads)
    return P(*(((None,) if stacked else ()) + (None, None, h, None)))


def act_spec(mesh: Mesh, *, seq_over_model: bool = False) -> P:
    """Activation spec for (B, S, d) tensors."""
    b = batch_axes(mesh)
    return P(b, "model" if seq_over_model else None, None)
