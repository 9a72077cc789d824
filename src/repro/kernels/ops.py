"""jit'd public wrappers for the Pallas kernels.

``_interpret`` is the one place that decides interpretation: off the TPU
the kernel body executes in Python per grid step (validating the exact TPU
program); on a TPU backend the kernels compile through Mosaic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import histogram as _hist
from repro.kernels import latent_attention as _la
from repro.kernels import moe_gemm as _mg
from repro.kernels import paged_attention as _pa
from repro.kernels import rg_lru as _rg
from repro.kernels import topk_router as _tk


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def moe_gemm(x, slot_w: dict, activation: str = "swiglu"):
    """Grouped expert FFN matching `repro.moe.dispatch.grouped_ffn`.
    x: (n_slots, T, d); slot_w: {"w_gate","w_up","w_down"}."""
    w_up = slot_w["w_up"].astype(x.dtype)
    w_gate = slot_w.get("w_gate", slot_w["w_up"]).astype(x.dtype)
    w_down = slot_w["w_down"].astype(x.dtype)
    return _mg.moe_gemm(x, w_gate, w_up, w_down, activation=activation,
                        interpret=_interpret())


def expert_histogram(expert_idx, num_experts: int):
    """(..., K) int32 expert assignments -> (num_experts,) int32 counts."""
    return _hist.histogram(expert_idx.reshape(-1).astype(jnp.int32),
                           num_experts, interpret=_interpret())


def histogram_offsets(idx, num_classes: int):
    """(N,) int32 class ids -> (counts, exclusive-prefix starts), both
    (num_classes,) int32 — the sort-based dispatch packer's slot layout."""
    return _hist.histogram_offsets(idx.reshape(-1).astype(jnp.int32),
                                   num_classes, interpret=_interpret())


def fused_topk_route(logits, top_k: int):
    """(T, E) router logits -> (idx, gates, probs, lse, counts) in one
    fused pass (see `repro.kernels.topk_router`)."""
    return _tk.fused_topk_route(logits, top_k, interpret=_interpret())


def rg_lru_scan(a, b, h0):
    """Linear recurrence h_t = a_t h_{t-1} + b_t (RG-LRU inner scan)."""
    return _rg.rg_lru_scan(a, b, h0, interpret=_interpret())


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           window: int = 0, mesh=None):
    """Fused paged GQA decode over the shared KV block pool — gather +
    online-softmax in one pass, no (B, M*bs, K, hd) intermediate (see
    `repro.kernels.paged_attention`). q: (B, K, G, hd).

    ``mesh``: the kernel runs under ``shard_map`` over it (Mosaic kernels
    cannot be partitioned automatically), sharded like the decode
    activations: slots (q, tables, lengths) over the batch axes when they
    divide evenly (``sharding.batch_axes_for``), the KV-head dim like the
    attention projections (``sharding.kv_head_axis``). The pool is split
    by head only: any slot's blocks may sit anywhere in it."""
    def kernel(q_, k_, v_, t_, l_):
        return _pa.paged_decode_attention(q_, k_, v_, t_, l_, window=window,
                                          interpret=_interpret())

    if mesh is None:
        return kernel(q, k_pool, v_pool, block_tables, lengths)
    from jax.sharding import PartitionSpec as P
    from repro.sharding import batch_axes_for, kv_head_axis, paged_pool_spec
    B, K = q.shape[:2]
    b = batch_axes_for(mesh, B)
    q_spec = P(b, kv_head_axis(mesh, K), None, None)
    kv_spec = paged_pool_spec(mesh, K, stacked=False)
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec, P(b, None),
                                   P(b)),
                         out_specs=q_spec, check_vma=False)(
        q, k_pool, v_pool, block_tables, lengths)


def latent_decode_attention(q, pool, block_tables, lengths, *, r: int,
                            scale: float, mesh=None):
    """Paged absorbed-MLA decode over the latent pool — the rows DMA'd a
    chunk of pages at a time, key and value read from the same row (see
    `repro.kernels.latent_attention`). q: (B, H, D); pool: (N, bs, D).

    ``mesh``: the kernel runs under ``shard_map`` over it, slots over the
    batch axes when they divide evenly, heads over "model" when it divides
    them; the pool (no head dim) whole on every device."""
    def kernel(q_, p_, t_, l_):
        return _la.latent_decode_attention(q_, p_, t_, l_, r=r, scale=scale,
                                           interpret=_interpret())

    if mesh is None:
        return kernel(q, pool, block_tables, lengths)
    from jax.sharding import PartitionSpec as P
    from repro.sharding import batch_axes_for, kv_head_axis
    B, H = q.shape[:2]
    b = batch_axes_for(mesh, B)
    q_spec = P(b, kv_head_axis(mesh, H), None)
    return jax.shard_map(kernel, mesh=mesh,
                         in_specs=(q_spec, P(), P(b, None), P(b)),
                         out_specs=q_spec, check_vma=False)(
        q, pool, block_tables, lengths)
