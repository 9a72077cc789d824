"""Top-k MoE router with load-balance auxiliary loss and router z-loss."""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.layers import truncated_normal_init


class RouterOutput(NamedTuple):
    expert_idx: jnp.ndarray     # (T, K) int32
    gates: jnp.ndarray          # (T, K) float32 (see ``topk_gates``)
    probs: jnp.ndarray          # (T, E) full softmax (for aux losses / stats)
    aux_loss: jnp.ndarray       # scalar
    z_loss: jnp.ndarray         # scalar


def init_router(key, d_model: int, moe: MoEConfig):
    return {"w": truncated_normal_init(key, (d_model, moe.num_experts), 0.02)}


def topk_gates(top_probs, moe: MoEConfig):
    """Gates of the k chosen experts from their softmax probabilities,
    renormalised over the k where ``moe.norm_topk_prob``."""
    if moe.norm_topk_prob:
        top_probs = top_probs / jnp.maximum(
            top_probs.sum(-1, keepdims=True), 1e-9)
    return top_probs


def route(params, moe: MoEConfig, x, impl: str = "dense") -> RouterOutput:
    """x: (T, d) token-major. Returns top-k assignment + losses.

    ``impl="fused"`` runs the Pallas fused softmax/top-k/histogram kernel
    (`repro.kernels.topk_router`) — one VMEM pass instead of three ops —
    and derives the aux losses from the kernel's histogram and logsumexp
    outputs. Assignments are bit-compatible with the dense path.
    """
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        params["w"].astype(jnp.float32))
    E = moe.num_experts
    if impl == "fused":
        from repro.kernels import ops as kernel_ops
        expert_idx, gates, probs, lse, counts = kernel_ops.fused_topk_route(
            logits, moe.top_k)
        gates = topk_gates(gates, moe)
        f = counts.astype(jnp.float32) / expert_idx.size
        aux = E * jnp.sum(f * probs.mean(axis=0)) * moe.router_aux_loss
        z = jnp.mean(jnp.square(lse)) * moe.router_z_loss
        return RouterOutput(expert_idx, gates, probs, aux, z)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_idx = jax.lax.top_k(probs, moe.top_k)
    gates = topk_gates(gates, moe)

    # Switch-style load-balance loss: E * sum_e f_e * p_e
    f = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (expert_idx.size))
    p_mean = probs.mean(axis=0)
    aux = E * jnp.sum(f * p_mean) * moe.router_aux_loss
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))) * moe.router_z_loss
    return RouterOutput(expert_idx.astype(jnp.int32), gates, probs, aux, z)


def expert_histogram(expert_idx, num_experts: int):
    """Token counts per expert. expert_idx: (..., K) -> (E,) float32."""
    return jnp.zeros((num_experts,), jnp.float32).at[
        expert_idx.reshape(-1)].add(1.0)
