"""jit-able train / prefill / decode step builders.

These are the functions the launcher lowers for the dry-run and executes
in examples — one source of truth for both.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.transformer import Runtime, forward
from repro.optim.adamw import AdamWState, adamw_update
from repro.train.loss import lm_loss


def make_train_step(cfg: ModelConfig, rt: Runtime, lr_fn=None,
                    remat: bool = False, microbatches: int = 1):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    ``remat``: wrap the forward in jax.checkpoint (activation recompute —
    trades the memory roofline term for ~1/3 more compute).
    ``microbatches``: split the global batch into sequential microbatches
    with gradient accumulation (lax.scan) — divides activation memory by
    the count at no recompute cost.
    """
    lr_fn = lr_fn or (lambda s: 3e-4)

    def loss_fn(params, batch, plan):
        fwd = forward
        if remat:
            fwd = jax.checkpoint(
                lambda p, b: forward(p, cfg, b, rt, mode="train", plan=plan),
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            logits, _, stats = fwd(params, batch)
        else:
            logits, _, stats = forward(params, cfg, batch, rt, mode="train",
                                       plan=plan)
        labels = batch["labels"]
        if cfg.input_mode == "mixed" and "prefix_embeds" in batch:
            # prefix embeddings carry no LM labels: score text positions only
            P = batch["prefix_embeds"].shape[1]
            logits = logits[:, P:]
        loss, metrics = lm_loss(logits, labels, batch.get("loss_mask"))
        if cfg.is_moe:
            loss = loss + stats["aux_loss"] + stats["z_loss"]
            metrics["aux_loss"] = stats["aux_loss"]
            metrics["expert_counts"] = stats["expert_counts"]
        return loss, metrics

    def grads_of(params, batch, plan):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch, plan)

    def train_step(params, opt_state: AdamWState, batch, plan=None):
        if microbatches > 1:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mb = jax.tree.map(split, batch)

            def body(carry, mbatch):
                acc = carry
                (loss, metrics), grads = grads_of(params, mbatch, plan)
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, (loss, metrics)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            gsum, (losses, metrics) = jax.lax.scan(body, zeros, mb)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = losses.mean()
            metrics = jax.tree.map(
                lambda m: m.mean(axis=0) if m.ndim else m.mean(), metrics)
        else:
            (loss, metrics), grads = grads_of(params, batch, plan)
        lr = lr_fn(opt_state.step)
        params, opt_state, gnorm = adamw_update(params, grads, opt_state, lr)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, rt: Runtime):
    """Batched prefill. The trailing ``slot_weights_back / slot_ready /
    target_plan`` triple is the overlapped-migration double-buffer view
    (``MoEConfig.overlap_migration``): all traced, so the engines can keep
    serving while a staged migration fills layer by layer — one compile
    covers idle and in-flight steps alike."""
    def prefill_step(params, batch, cache, plan=None, predicted_idx=None,
                     slot_weights=None, slot_weights_back=None,
                     slot_ready=None, target_plan=None, resched=None):
        logits, cache, stats = forward(params, cfg, batch, rt, mode="prefill",
                                       cache=cache, plan=plan,
                                       predicted_idx=predicted_idx,
                                       slot_weights=slot_weights,
                                       slot_weights_back=slot_weights_back,
                                       slot_ready=slot_ready,
                                       target_plan=target_plan,
                                       resched=resched)
        return logits, cache, stats
    return prefill_step


def make_prefill_replan_step(cfg: ModelConfig, rt: Runtime):
    """Fused predict -> plan -> dispatch serving step (one XLA program).

    Runs the prefill with the CURRENT placement plan, then plans the NEXT
    batch's duplication in-graph from this batch's expert histogram via
    the jittable Algorithm 1 (`duplicate_experts_jax`, vmapped over
    layers) — no host round-trip per prediction interval.

    Stays on the per-step gather pool: the replica store is filled by a
    HOST-orchestrated migration (plan diffing is a host decision), which
    would defeat the point of planning in-graph.
    """
    from repro.core.duplication import duplicate_experts_jax

    moe = cfg.moe

    def step(params, batch, cache, plan=None, predicted_idx=None):
        logits, cache, stats = forward(params, cfg, batch, rt, mode="prefill",
                                       cache=cache, plan=plan,
                                       predicted_idx=predicted_idx)
        counts = stats["expert_counts"]                      # (L, E)
        next_plan = jax.vmap(
            lambda c: duplicate_experts_jax(
                c, rt.ep_ranks, moe.duplication_slots, moe.max_copies)
        )(counts)
        return logits, cache, stats, next_plan

    return step


def make_slot_prefill_step(cfg: ModelConfig, rt: Runtime):
    """Continuous-batching prefill: one request padded to a fixed bucket.

    Differences from ``make_prefill_step``: logits are gathered at the
    request's REAL last prompt token (``last_pos``), and ``token_weight``
    masks padding out of the MoE expert histograms. Everything is traced,
    so one compile per prompt-length bucket."""
    def prefill_step(params, batch, cache, plan=None, predicted_idx=None,
                     last_pos=None, token_weight=None, slot_weights=None,
                     slot_weights_back=None, slot_ready=None,
                     target_plan=None, resched=None):
        logits, cache, stats = forward(params, cfg, batch, rt, mode="prefill",
                                       cache=cache, plan=plan,
                                       predicted_idx=predicted_idx,
                                       last_pos=last_pos,
                                       token_weight=token_weight,
                                       slot_weights=slot_weights,
                                       slot_weights_back=slot_weights_back,
                                       slot_ready=slot_ready,
                                       target_plan=target_plan,
                                       resched=resched)
        with jax.named_scope("lm_head"):
            next_tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
        return next_tok, logits, cache, stats
    return prefill_step


def make_paged_decode_step(cfg: ModelConfig, rt: Runtime):
    """Continuous-batching decode over the paged KV block pool.

    All slots advance one token at their OWN position (``lengths`` is a
    traced (B,) vector — no recompilation as requests join/leave). Returns
    greedy next tokens for every slot; the engine masks idle slots."""
    def decode_step(params, tokens, pool, block_tables, lengths, plan=None,
                    token_weight=None, slot_weights=None,
                    slot_weights_back=None, slot_ready=None,
                    target_plan=None, resched=None):
        logits, pool, stats = forward(params, cfg, {"tokens": tokens}, rt,
                                      mode="decode", cache=pool,
                                      cache_len=lengths, plan=plan,
                                      block_tables=block_tables,
                                      token_weight=token_weight,
                                      slot_weights=slot_weights,
                                      slot_weights_back=slot_weights_back,
                                      slot_ready=slot_ready,
                                      target_plan=target_plan,
                                      resched=resched)
        with jax.named_scope("lm_head"):
            next_tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
        return next_tok, logits, pool, stats
    return decode_step


def make_decode_step(cfg: ModelConfig, rt: Runtime):
    def decode_step(params, tokens, cache, cache_len, plan=None,
                    slot_weights=None, slot_weights_back=None,
                    slot_ready=None, target_plan=None, resched=None):
        logits, cache, stats = forward(params, cfg, {"tokens": tokens}, rt,
                                       mode="decode", cache=cache,
                                       cache_len=cache_len, plan=plan,
                                       slot_weights=slot_weights,
                                       slot_weights_back=slot_weights_back,
                                       slot_ready=slot_ready,
                                       target_plan=target_plan,
                                       resched=resched)
        next_tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
        return next_tok, logits, cache, stats
    return decode_step
