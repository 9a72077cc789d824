"""Model assembly for every assigned architecture family.

Uniform-stack families (dense / moe / vlm / ssm / audio-encoder+decoder)
scan over stacked layer params so trace/compile time is depth-independent.
The hybrid family (recurrentgemma) has heterogeneous blocks and unrolls a
python loop over its (short) layer stack.

Execution modes:
  train    — full causal pass, logits over the whole sequence, no cache.
  prefill  — causal pass that also fills the cache; returns last-position logits.
  decode   — one token against the cache (the ``serve_step`` of the assignment).

MoE layers run one of three paths, selected by ``Runtime``:
  dense (reference, single device), EP shard_map all_to_all dispatch
  (train/prefill; placement-aware duplication), or EP replicated-token
  dispatch (decode, tokens replicated over the model axis, psum combine).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.placement import PlacementPlan, identity_plan
from repro.models import attention as attn
from repro.models import griffin, rwkv6
from repro.models.layers import (apply_norm, dense, embed, ffn, init_dense,
                                 init_embedding, init_ffn, init_norm, unembed)
from repro.models.moe import init_moe_block, moe_ffn_dense
from repro.moe import dispatch as ep
from repro.moe.router import route


class Runtime(NamedTuple):
    """Execution-context knobs (static except plan/predicted)."""
    mesh: Optional[Mesh] = None
    ep: bool = False                     # expert-parallel shard_map dispatch
    ep_axis: str = "model"
    ep_ranks: int = 1
    use_duplication: bool = False
    plan: Optional[PlacementPlan] = None          # stacked (L, ...) plan arrays
    predicted_idx: Optional[jnp.ndarray] = None   # (L, T, K) token-to-expert preds
    use_kernel: bool = False
    window_override: int = 0             # force sliding window (long-context decode)
    decode_expert_tp: bool = False       # 2D expert sharding (EP x f-TP) for decode

    def window(self, cfg: ModelConfig) -> int:
        return self.window_override or cfg.sliding_window


def _batch_axes(mesh):
    return tuple(n for n in mesh.axis_names if n in ("pod", "data"))


def constrain_acts(x, rt: "Runtime", seq_shard: bool = False):
    """Pin (B, S, d) activations to batch-sharded/replicated-d layout.

    Without an explicit constraint GSPMD is free to replicate activations
    across the batch axes inside the layer scan — measured as an
    8.6 GB/layer all-gather on qwen train_4k (EXPERIMENTS.md §Perf #2).

    ``seq_shard``: additionally shard the sequence dim over "model"
    (sequence parallelism). Used for MoE archs in train/prefill, whose EP
    dispatch shard_map consumes seq-sharded tokens — a batch-only
    constraint would force a full-activation reshard each layer (measured
    as a 6.6 -> 10.1s collective REGRESSION on arctic, §Perf sweep).
    """
    if rt.mesh is None or x.ndim != 3:
        return x
    b = _batch_axes(rt.mesh)
    if not b:
        return x
    n_b = 1
    for a in b:
        n_b *= rt.mesh.shape[a]
    if x.shape[0] % n_b != 0:
        return x
    seq = None
    if seq_shard and x.shape[1] % rt.mesh.shape["model"] == 0:
        seq = "model"
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rt.mesh, P(b, seq, None)))


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, kind: str):
    keys = jax.random.split(key, 6)
    p: Dict[str, Any] = {"ln1": init_norm(cfg.norm, cfg.d_model),
                         "ln2": init_norm(cfg.norm, cfg.d_model)}
    if kind in ("attn", "encoder", "decoder"):
        if cfg.attention == "mla":
            p["attn"] = attn.init_mla(keys[0], cfg)
        else:
            p["attn"] = attn.init_gqa(keys[0], cfg)
        if kind == "decoder":
            p["cross"] = attn.init_gqa(keys[1], cfg)
            p["ln_cross"] = init_norm(cfg.norm, cfg.d_model)
        if cfg.is_moe:
            p["moe"] = init_moe_block(keys[2], cfg)
        else:
            p["ffn"] = init_ffn(keys[2], cfg.d_model, cfg.d_ff, cfg.activation)
    elif kind == "rwkv":
        p["time_mix"] = rwkv6.init_time_mix(keys[0], cfg)
        p["channel_mix"] = rwkv6.init_channel_mix(keys[2], cfg)
    elif kind == "recurrent":
        p["rec"] = griffin.init_recurrent_block(keys[0], cfg)
        p["ffn"] = init_ffn(keys[2], cfg.d_model, cfg.d_ff, cfg.activation)
    elif kind == "local":
        p["attn"] = attn.init_gqa(keys[0], cfg)
        p["ffn"] = init_ffn(keys[2], cfg.d_model, cfg.d_ff, cfg.activation)
    else:
        raise ValueError(kind)
    return p


def dense_layer_cfg(cfg: ModelConfig) -> ModelConfig:
    """The config of the leading dense layers (``cfg.first_k_dense``):
    the model's, with a dense FFN of width ``d_ff`` in place of experts."""
    return dataclasses.replace(cfg, moe=None, first_k_dense=0)


def _layer_kind(cfg: ModelConfig, layer_idx: int) -> str:
    if cfg.family == "ssm":
        return "rwkv"
    if cfg.family == "hybrid":
        return cfg.block_pattern[layer_idx % len(cfg.block_pattern)]
    return "attn"


def init_model(key, cfg: ModelConfig, dtype=jnp.float32):
    """Parameters in ``dtype``. Stacked layers are built one layer at a
    time (``lax.map``) and cast per layer, so tracing this under jit never
    needs more than one layer's worth of temporaries."""
    keys = jax.random.split(key, 8)

    def cast(tree):
        return jax.tree.map(lambda a: a.astype(dtype), tree)

    def stack(layer_keys, kind, layer_cfg=cfg):
        return jax.lax.map(
            lambda k: cast(_init_layer(k, layer_cfg, kind)), layer_keys)

    params: Dict[str, Any] = {"embed": init_embedding(keys[0], cfg.vocab_size,
                                                      cfg.d_model)}
    params["final_norm"] = init_norm(cfg.norm, cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[1], cfg.d_model, cfg.vocab_size)

    if cfg.family == "hybrid":
        layer_keys = jax.random.split(keys[2], cfg.num_layers)
        params["hybrid_layers"] = [
            _init_layer(layer_keys[i], cfg, _layer_kind(cfg, i))
            for i in range(cfg.num_layers)]
    else:
        kind = _layer_kind(cfg, 0)
        layer_keys = jax.random.split(keys[2], cfg.num_moe_layers)
        params["layers"] = stack(layer_keys, kind)
        if cfg.first_k_dense:
            params["dense_layers"] = stack(
                jax.random.split(keys[4], cfg.first_k_dense), kind,
                dense_layer_cfg(cfg))

    if cfg.is_encdec:
        enc = cfg.encoder
        import dataclasses
        enc_cfg = dataclasses.replace(
            cfg, num_layers=enc.num_layers, d_model=enc.d_model,
            num_heads=enc.num_heads, num_kv_heads=enc.num_kv_heads,
            d_ff=enc.d_ff, moe=None, encoder=None, attention="gqa",
            head_dim=enc.d_model // enc.num_heads)
        ekeys = jax.random.split(keys[3], enc.num_layers)
        params["enc_layers"] = stack(ekeys, "encoder", enc_cfg)
        params["enc_norm"] = init_norm(cfg.norm, enc.d_model)
        # decoder layers get cross-attention
        dkeys = jax.random.split(keys[2], cfg.num_layers)
        params["layers"] = stack(dkeys, "decoder")
    return cast(params)


def init_params(key, cfg: ModelConfig, *, mesh: Optional[Mesh] = None):
    """Serving weights: ``init_model``'s values in bf16, built under jit
    straight into their ``sharding.param_specs`` layout on ``mesh``
    (unsharded without one). No device ever holds more than its share,
    and float32 values exist only one layer at a time (``init_model``
    casts per layer)."""
    def build(k):
        return init_model(k, cfg, jnp.bfloat16)

    if mesh is None:
        return jax.jit(build)(key)
    from repro.sharding import make_shardings, param_specs
    shapes = jax.eval_shape(build, key)
    shardings = make_shardings(mesh, param_specs(shapes, mesh=mesh))
    return jax.jit(build, out_shardings=shardings)(key)


# ---------------------------------------------------------------------------
# caches / states
# ---------------------------------------------------------------------------

def cache_len_for(cfg: ModelConfig, rt: Runtime, max_len: int) -> int:
    w = rt.window(cfg)
    return min(max_len, w) if w else max_len


def init_cache(cfg: ModelConfig, rt: Runtime, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Stacked (over layers) cache pytree for prefill/decode. The leading
    dense layers' caches (``cfg.first_k_dense``) sit under ``"dense"``,
    stacked over those layers alone."""
    L = cfg.num_layers
    clen = cache_len_for(cfg, rt, max_len)

    def stack(tree, n=L):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), tree)

    if cfg.family == "ssm":
        return stack(rwkv6.init_rwkv_state(cfg, batch))
    if cfg.family == "hybrid":
        caches = []
        for i in range(L):
            kind = _layer_kind(cfg, i)
            if kind == "recurrent":
                caches.append(griffin.init_recurrent_state(cfg, batch, dtype))
            else:
                caches.append(attn.init_gqa_cache(
                    cfg, batch, min(max_len, cfg.local_window), dtype))
        return caches
    if cfg.attention == "mla":
        one = attn.init_mla_cache(cfg, batch, clen, dtype)
    else:
        one = attn.init_gqa_cache(cfg, batch, clen, dtype)
    c = stack(one, cfg.num_moe_layers)
    if cfg.first_k_dense:
        c = {**c, "dense": stack(one, cfg.first_k_dense)}
    if cfg.is_encdec:
        enc = cfg.encoder
        c = {"self": c,
             "cross_k": jnp.zeros((L, batch, enc.max_source_len,
                                   cfg.num_kv_heads, cfg.head_dim), dtype),
             "cross_v": jnp.zeros((L, batch, enc.max_source_len,
                                   cfg.num_kv_heads, cfg.head_dim), dtype)}
    return c


# ---------------------------------------------------------------------------
# MoE layer execution paths
# ---------------------------------------------------------------------------

def moe_apply(layer_p, cfg: ModelConfig, x, rt: Runtime, plan_l,
              predicted_l, decode: bool, token_weight=None,
              slot_w_l=None, resched_l=None, migration_l=None):
    """x: (B, S, d). Returns (y, expert_counts (E,), slot_counts, aux, z,
    dropped, overflow).

    ``resched_l``: optional (E, C_max) int32 reschedule quota for this
    layer (``repro.schedule``) — replica choice follows the scheduler's
    per-copy shares and capacity-overflow tokens get a rescue dispatch
    round. Traced, so quota refreshes never recompile.

    ``token_weight``: optional (B, S) per-token weight for the expert
    histogram — the continuous-batching engine passes the active/padding
    mask so estimator inputs only count REAL tokens (padded prefill
    positions and idle decode slots still flow through the FFN but must
    not skew the observed distribution).

    ``slot_w_l``: optional {name: (S_global, ...)} resident slot weights
    for this layer (one ``repro.runtime.ReplicaStore`` layer slice) —
    sharded over the EP axis so dispatch reads replica weights from
    device memory instead of re-gathering a pool every step.

    ``migration_l``: optional ``(ready, target_plan, back_weights)`` for
    this layer during an overlapped migration (``MoEConfig.
    overlap_migration``): once the layer's staged fill is ready, dispatch
    reads the back buffer under the target plan, until then the live
    pair. The ``lax.cond`` wraps the dispatch itself, not a choice of
    weights, so neither buffer is copied (a cond that returns the chosen
    weights materializes them: a store-sized temporary per step).
    """
    moe = cfg.moe
    B, S, d = x.shape
    if not rt.ep:
        y, router_out = moe_ffn_dense(layer_p["moe"], cfg, x)
        w = (jnp.ones((B * S * moe.top_k,), jnp.float32)
             if token_weight is None
             else jnp.repeat(token_weight.reshape(-1).astype(jnp.float32),
                             moe.top_k))
        counts = jnp.zeros((moe.num_experts,), jnp.float32).at[
            router_out.expert_idx.reshape(-1)].add(w)
        return (y, counts, counts, router_out.aux_loss, router_out.z_loss,
                jnp.asarray(0, jnp.int32),    # dense path never drops
                jnp.asarray(0, jnp.int32))

    mesh = rt.mesh
    baxes = _batch_axes(mesh)
    # small batches (e.g. long-context decode, B=1) replicate over the
    # batch axes instead of sharding them
    n_b = 1
    for a in baxes:
        n_b *= mesh.shape[a]
    if B % n_b != 0:
        baxes = ()
    plan_l = plan_l if plan_l is not None else identity_plan(
        moe.num_experts, rt.ep_ranks, moe.duplication_slots, moe.max_copies)

    # 2D expert sharding for decode (EXPERIMENTS.md §Perf cycle 2):
    # d_ff additionally shards over the batch axes so weights stay
    # resident (no ZeRO re-gather per token); tokens replicate and one
    # psum over (batch axes + model) combines f-partials + slot results.
    # Works regardless of batch divisibility (tokens replicate anyway),
    # so use the FULL batch axes, not the divisibility-filtered ones.
    tp_axes = _batch_axes(mesh)
    n_tp = 1
    for a in tp_axes:
        n_tp *= mesh.shape[a]
    tp_mode = (decode and rt.decode_expert_tp and bool(tp_axes)
               and moe.d_ff_expert % n_tp == 0)
    if tp_mode:
        slot_w_l = None       # 2D expert sharding keeps the gather path
        migration_l = None
    expert_specs = P("model", None, None)
    if decode:
        if tp_mode:
            x_spec = P(None, None, None)
            expert_specs = {"w_gate": P("model", None, tp_axes),
                            "w_up": P("model", None, tp_axes),
                            "w_down": P("model", tp_axes, None)}
        else:
            x_spec = P(baxes if baxes else None, None, None)
        from functools import partial as _partial
        dispatch_fn = _partial(ep.ep_moe_ffn_replicated,
                               tp_axis=tp_axes if tp_mode else ())
    else:
        x_spec = P(baxes if baxes else None, "model", None)
        dispatch_fn = ep.ep_moe_ffn

    # kernel runs fuse routing (softmax/top-k/histogram) into one Pallas
    # pass when the sort dispatch pipeline is active
    router_impl = ("fused" if rt.use_kernel and moe.dispatch_impl == "sort"
                   else "dense")

    def inner(x_blk, router_w, experts_w, plan, pred, w_blk, slot_blk, quota,
              mig):
        t = x_blk.reshape(-1, x_blk.shape[-1])
        with jax.named_scope("moe.route"):
            router_out = route(router_w, moe, t, impl=router_impl)

        def dispatch(plan, slots):
            return dispatch_fn(
                t, router_out, experts_w, plan, moe,
                axis_name=rt.ep_axis, ep_ranks=rt.ep_ranks,
                activation=cfg.activation,
                use_duplication=rt.use_duplication,
                predicted_idx=(pred.reshape(-1, moe.top_k)
                               if pred is not None else None),
                use_kernel=rt.use_kernel,
                slot_weights=slots,
                resched_quota=quota)

        if mig is None:
            y, stats = dispatch(plan, slot_blk)
        else:
            ready, tplan, back_blk = mig
            y, stats = jax.lax.cond(ready,
                                    lambda: dispatch(tplan, back_blk),
                                    lambda: dispatch(plan, slot_blk))
        counts, slots = stats.expert_counts, stats.slot_counts
        aux, z, dropped = stats.aux_loss, stats.z_loss, stats.dropped
        overflow = stats.overflow
        if w_blk is not None:
            # weighted histogram replaces the dispatch count (padding /
            # idle-slot tokens carry weight 0). Prefill tokens are
            # seq-sharded over the model axis, so re-psum there; decode
            # tokens are replicated over it (counts already global).
            wk = jnp.repeat(w_blk.reshape(-1).astype(jnp.float32), moe.top_k)
            counts = jnp.zeros((moe.num_experts,), jnp.float32).at[
                router_out.expert_idx.reshape(-1)].add(wk)
            if not decode:
                counts = jax.lax.psum(counts, rt.ep_axis)
        if baxes and not tp_mode:
            # stats are psum'd over "model" inside dispatch only; in
            # tp_mode tokens are replicated so stats are already global
            counts = jax.lax.psum(counts, baxes)
            slots = jax.lax.psum(slots, baxes)
            aux = jax.lax.pmean(aux, baxes)
            z = jax.lax.pmean(z, baxes)
            dropped = jax.lax.psum(dropped, baxes)
            overflow = jax.lax.psum(overflow, baxes)
        return y.reshape(x_blk.shape), counts, slots, aux, z, dropped, overflow

    plan_specs = PlacementPlan(P(), P(), P(), P())
    pred_spec = None if predicted_l is None else x_spec
    w_spec = None if token_weight is None else P(*x_spec[:-1])
    slot_spec = None if slot_w_l is None else P("model", None, None)
    resched_spec = None if resched_l is None else P()
    mig_spec = None if migration_l is None else (P(), plan_specs, slot_spec)
    y, counts, slot_counts, aux, z, dropped, overflow = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(x_spec, P(), expert_specs, plan_specs, pred_spec, w_spec,
                  slot_spec, resched_spec, mig_spec),
        out_specs=(x_spec, P(), P(), P(), P(), P(), P()),
        check_vma=False,
    )(x, layer_p["moe"]["router"], layer_p["moe"]["experts"], plan_l,
      predicted_l, token_weight, slot_w_l, resched_l, migration_l)

    if "shared" in layer_p["moe"]:
        with jax.named_scope("moe.shared"):
            y = y + ffn(layer_p["moe"]["shared"], x, cfg.activation)
    if "dense" in layer_p["moe"]:
        y = y + ffn(layer_p["moe"]["dense"], x, cfg.activation)
    return y, counts, slot_counts, aux, z, dropped, overflow


# ---------------------------------------------------------------------------
# layer bodies
# ---------------------------------------------------------------------------

def _zero_stats(cfg):
    E = cfg.moe.num_experts if cfg.is_moe else 1
    return (jnp.zeros((E,), jnp.float32), jnp.zeros((E,), jnp.float32),
            jnp.asarray(0.0, jnp.float32), jnp.asarray(0.0, jnp.float32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))


def _attn_layer(layer_p, cfg, x, positions, rt, *, cache=None, cache_len=None,
                mode="train", enc_out=None, plan_l=None, predicted_l=None,
                block_tables=None, token_weight=None, slot_w_l=None,
                resched_l=None, migration_l=None):
    """Generic attention+FFN layer for dense/moe/vlm/audio-decoder."""
    window = rt.window(cfg)
    h = apply_norm(cfg.norm, layer_p["ln1"], x)
    new_cache = cache
    if mode == "train":
        if cfg.attention == "mla":
            a = attn.mla_attention(layer_p["attn"], cfg, h, positions,
                                   window=window)
        else:
            a = attn.gqa_attention(layer_p["attn"], cfg, h, positions,
                                   window=window)
    elif mode == "prefill":
        sub = cache["self"] if cfg.is_encdec else cache
        if cfg.attention == "mla":
            a, sub = attn.mla_prefill(layer_p["attn"], cfg, h, positions, sub,
                                      window=window)
        else:
            a, sub = attn.gqa_prefill(layer_p["attn"], cfg, h, positions, sub,
                                      window=window)
        new_cache = dict(cache, self=sub) if cfg.is_encdec else sub
    else:  # decode
        sub = cache["self"] if cfg.is_encdec else cache
        if block_tables is not None:
            # continuous batching keeps caches linear (window_override =
            # max_len for sizing) but must still MASK to the architectural
            # sliding window, or paged decode diverges from windowed
            # serving. cfg.paged_attn_impl selects the fused Pallas
            # one-pass kernel or the materialize-then-attend gather oracle
            if cfg.attention == "mla":
                a, sub = attn.mla_decode_paged(layer_p["attn"], cfg, h, sub,
                                               block_tables, cache_len,
                                               mesh=rt.mesh)
            else:
                a, sub = attn.gqa_decode_paged(layer_p["attn"], cfg, h, sub,
                                               block_tables, cache_len,
                                               window=cfg.sliding_window,
                                               mesh=rt.mesh)
        elif cfg.attention == "mla":
            a, sub = attn.mla_decode(layer_p["attn"], cfg, h, sub, cache_len,
                                     window=window)
        elif jnp.ndim(cache_len) == 1:
            # continuous batching: per-slot positions over a slotted cache
            a, sub = attn.gqa_decode_multi(layer_p["attn"], cfg, h, sub,
                                           cache_len,
                                           window=cfg.sliding_window)
        else:
            a, sub = attn.gqa_decode_windowed(layer_p["attn"], cfg, h, sub,
                                              cache_len, window=window)
        new_cache = dict(cache, self=sub) if cfg.is_encdec else sub
    x = x + a

    if cfg.is_encdec and "cross" in layer_p:
        h = apply_norm(cfg.norm, layer_p["ln_cross"], x)
        if mode == "decode":
            ck, cv = new_cache["cross_k"], new_cache["cross_v"]
            B = x.shape[0]
            q = dense(layer_p["cross"]["wq"], h).reshape(
                B, 1, cfg.num_heads, cfg.head_dim)
            c = attn.decode_attention(q, ck, cv, cache_len=ck.shape[1])
            c = dense(layer_p["cross"]["wo"], c.reshape(B, 1, -1))
        else:
            c, ck, cv = cross_attention(layer_p["cross"], cfg, h, enc_out)
            if mode == "prefill":
                new_cache = dict(new_cache, cross_k=ck, cross_v=cv)
        x = x + c

    h = apply_norm(cfg.norm, layer_p["ln2"], x)
    if cfg.is_moe:
        y, counts, slots, aux, z, dropped, overflow = moe_apply(
            layer_p, cfg, h, rt, plan_l, predicted_l,
            decode=(mode == "decode"), token_weight=token_weight,
            slot_w_l=slot_w_l, resched_l=resched_l, migration_l=migration_l)
        stats = (counts, slots, aux, z, dropped, overflow)
    else:
        with jax.named_scope("ffn.dense"):
            y = ffn(layer_p["ffn"], h, cfg.activation)
        stats = _zero_stats(cfg)
    return x + y, new_cache, stats


def cross_attention(params, cfg: ModelConfig, x, enc_out):
    """Full (non-causal) cross attention. Returns (out, k, v) for caching."""
    B, S, _ = x.shape
    Se = enc_out.shape[1]
    q = dense(params["wq"], x).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = dense(params["wk"], enc_out).reshape(B, Se, cfg.num_kv_heads, cfg.head_dim)
    v = dense(params["wv"], enc_out).reshape(B, Se, cfg.num_kv_heads, cfg.head_dim)
    out = attn.chunked_attention(q, k, v, causal=False)
    return dense(params["wo"], out.reshape(B, S, -1)), k, v


def _rwkv_layer(layer_p, cfg, x, state):
    h = apply_norm(cfg.norm, layer_p["ln1"], x)
    a, new_tm = rwkv6.time_mix(layer_p["time_mix"], cfg, h,
                               {"shift_tm": state["shift_tm"],
                                "wkv": state["wkv"]})
    x = x + a
    h = apply_norm(cfg.norm, layer_p["ln2"], x)
    y, new_shift_cm = rwkv6.channel_mix(layer_p["channel_mix"], h,
                                        state["shift_cm"])
    new_state = {"shift_tm": new_tm["shift_tm"], "wkv": new_tm["wkv"],
                 "shift_cm": new_shift_cm}
    return x + y, new_state


def _hybrid_layer(layer_p, cfg, x, positions, kind, state, rt, mode, cache_len):
    h = apply_norm(cfg.norm, layer_p["ln1"], x)
    if kind == "recurrent":
        a, new_state = griffin.recurrent_block(layer_p["rec"], cfg, h, state)
    else:  # local attention
        if mode == "train":
            a = attn.gqa_attention(layer_p["attn"], cfg, h, positions,
                                   window=cfg.local_window)
            new_state = state
        elif mode == "prefill":
            a, new_state = attn.gqa_prefill_windowed(
                layer_p["attn"], cfg, h, positions, state,
                window=cfg.local_window)
        else:
            a, new_state = attn.gqa_decode_windowed(
                layer_p["attn"], cfg, h, state, cache_len,
                window=cfg.local_window)
    x = x + a
    h = apply_norm(cfg.norm, layer_p["ln2"], x)
    return x + ffn(layer_p["ffn"], h, cfg.activation), new_state


# ---------------------------------------------------------------------------
# full model forward
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelConfig, batch):
    """tokens (+ optional prefix embeddings) -> (B, S, d), positions."""
    tok = embed(params["embed"], batch["tokens"])
    if cfg.input_mode == "mixed" and "prefix_embeds" in batch:
        x = jnp.concatenate([batch["prefix_embeds"].astype(tok.dtype), tok],
                            axis=1)
    else:
        x = tok
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x.astype(jnp.bfloat16), positions


def _encode(params, cfg: ModelConfig, frames, rt: Runtime):
    """Audio encoder: bidirectional transformer over stub frame embeddings."""
    enc = cfg.encoder
    x = frames.astype(jnp.bfloat16)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    import dataclasses
    enc_cfg = dataclasses.replace(
        cfg, num_layers=enc.num_layers, d_model=enc.d_model,
        num_heads=enc.num_heads, num_kv_heads=enc.num_kv_heads, d_ff=enc.d_ff,
        moe=None, encoder=None, attention="gqa",
        head_dim=enc.d_model // enc.num_heads)

    def body(h, layer_p):
        z = apply_norm(cfg.norm, layer_p["ln1"], h)
        q, k, v = attn.gqa_project(layer_p["attn"], enc_cfg, z, positions)
        a = attn.chunked_attention(q, k, v, causal=False)
        a = dense(layer_p["attn"]["wo"], a.reshape(B, S, -1))
        h = h + a
        z = apply_norm(cfg.norm, layer_p["ln2"], h)
        return h + ffn(layer_p["ffn"], z, cfg.activation), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return apply_norm(cfg.norm, params["enc_norm"], x)


def _logits(params, cfg: ModelConfig, x):
    h = apply_norm(cfg.norm, params["final_norm"], x)
    if cfg.tie_embeddings:
        return unembed(params["embed"], h)
    return dense(params["lm_head"], h)


def forward(params, cfg: ModelConfig, batch, rt: Runtime, *, mode: str,
            cache=None, cache_len=None, plan=None, predicted_idx=None,
            block_tables=None, last_pos=None, token_weight=None,
            slot_weights=None, slot_weights_back=None, slot_ready=None,
            target_plan=None, resched=None):
    """Unified entry. Returns (logits, new_cache, stats_dict).

    mode=train:   logits (B, S, V) over the full sequence.
    mode=prefill: logits (B, 1, V) for the last position; fills cache.
    mode=decode:  batch={"tokens": (B, 1)}; logits (B, 1, V).

    ``plan`` / ``predicted_idx`` override rt.plan / rt.predicted_idx so the
    serving loop can swap placement plans per prediction interval without
    recompiling (they are traced arguments, not closure constants).

    Continuous-batching extensions (all traced, all optional):
      ``cache_len``     — decode position; a scalar (legacy synchronous
                          batch) or a (B,) vector of per-slot lengths.
      ``block_tables``  — (B, M) physical-block map; selects the paged-KV
                          decode path (cache = block pool).
      ``last_pos``      — (B,) index of each request's last REAL prompt
                          token; prefill logits are gathered there instead
                          of at the padded end.
      ``token_weight``  — (B, S) weight for MoE expert histograms (0 for
                          padding / idle slots).
      ``slot_weights``  — stacked {name: (L, S_global, ...)} resident
                          replica slot weights (``ReplicaStore.weights``);
                          when given, EP dispatch reads replica weights
                          from device memory instead of all_gathering a
                          pool every step. Traced, so migration commits
                          (new contents, same shapes) never recompile.

    Overlapped-migration extensions (``MoEConfig.overlap_migration``; all
    traced, engines pass live==back + all-False ready when no migration is
    in flight so the jit signature never changes):
      ``slot_weights_back`` — the in-flight double buffer the
                          ``LayerStagedExecutor`` is filling toward the
                          target plan.
      ``slot_ready``    — (L,) bool per-layer ready-version vector: True
                          once layer l's staged fill committed.
      ``target_plan``   — stacked plan the migration is moving toward.
    Each scanned layer picks (plan_l, slots_l) from the OLD pair until its
    ready bit flips, then from the target pair — every layer always sees a
    consistent plan/weights view, so the async path is bit-exact with the
    synchronous one at every intermediate state.
    """
    enc_out = None
    if cfg.is_encdec and mode != "decode":
        enc_out = _encode(params, cfg, batch["frames"], rt)

    if mode == "decode":
        B = batch["tokens"].shape[0]
        x = embed(params["embed"], batch["tokens"]).astype(jnp.bfloat16)
        cl = jnp.asarray(cache_len, jnp.int32)
        positions = (cl[:, None] if cl.ndim == 1
                     else jnp.full((B, 1), cache_len, jnp.int32))
    else:
        x, positions = _embed_inputs(params, cfg, batch)
    x = constrain_acts(x, rt)

    L = cfg.num_layers
    stats = {"expert_counts": None, "aux_loss": 0.0, "z_loss": 0.0}

    if cfg.family == "hybrid":
        new_caches = []
        for i in range(L):
            kind = _layer_kind(cfg, i)
            st = None if cache is None else cache[i]
            if mode == "train":
                st = (griffin.init_recurrent_state(cfg, x.shape[0])
                      if kind == "recurrent" else
                      attn.init_gqa_cache(cfg, x.shape[0], 1))
            x, new_st = _hybrid_layer(params["hybrid_layers"][i], cfg, x,
                                      positions, kind, st, rt, mode, cache_len)
            x = constrain_acts(x, rt)
            new_caches.append(new_st)
        new_cache = None if mode == "train" else new_caches

    elif cfg.family == "ssm":
        if cache is None:
            state0 = rwkv6.init_rwkv_state(cfg, x.shape[0])
            cache_l = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (L,) + a.shape), state0)
        else:
            cache_l = cache

        # constraints gain 7.2x at train but cost 12% at prefill (the
        # state-scan layout differs) — apply them for training only
        # (EXPERIMENTS.md §Perf sweep note)
        use_c = mode == "train"

        def body(h, xs):
            layer_p, st = xs
            h = constrain_acts(h, rt) if use_c else h
            h, new_st = _rwkv_layer(layer_p, cfg, h, st)
            return constrain_acts(h, rt) if use_c else h, new_st

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache_l))
        if mode == "train":
            new_cache = None

    else:
        plan = plan if plan is not None else rt.plan
        pred = predicted_idx if predicted_idx is not None else rt.predicted_idx

        seq_shard = cfg.is_moe and mode != "decode"
        overlap = (cfg.is_moe and cfg.moe.overlap_migration
                   and slot_weights is not None
                   and slot_weights_back is not None
                   and slot_ready is not None and target_plan is not None
                   and plan is not None)

        dense_cache = None
        if cfg.first_k_dense:
            # the leading dense layers: a scan of their own, over their own
            # params and caches, ahead of the uniform stack
            dcfg = dense_layer_cfg(cfg)
            if cache is not None:
                cache = dict(cache)
                dense_cache = cache.pop("dense")

            def dense_body(h, xs):
                layer_p, cache_l = xs
                with jax.named_scope("layer.body"):
                    h = constrain_acts(h, rt, seq_shard)
                    h, new_c, _ = _attn_layer(
                        layer_p, dcfg, h, positions, rt, cache=cache_l,
                        cache_len=cache_len, mode=mode, enc_out=enc_out,
                        block_tables=block_tables)
                    return constrain_acts(h, rt, seq_shard), new_c

            with jax.named_scope("layers"):
                x, dense_cache = jax.lax.scan(
                    dense_body, x, (params["dense_layers"], dense_cache))

        def body(h, xs):
            (layer_p, cache_l, plan_l, pred_l, slot_l, back_l, ready_l,
             tplan_l, resched_l) = xs
            # the body's own ops (norms, residual adds) fall under
            # "layer.body"; ops under "layers" and outside it are the scan
            # slicing its xs and writing its ys (the stacked caches)
            with jax.named_scope("layer.body"):
                h = constrain_acts(h, rt, seq_shard)
                h, new_c, st = _attn_layer(
                    layer_p, cfg, h, positions, rt, cache=cache_l,
                    cache_len=cache_len, mode=mode, enc_out=enc_out,
                    plan_l=plan_l, predicted_l=pred_l,
                    block_tables=block_tables, token_weight=token_weight,
                    slot_w_l=slot_l, resched_l=resched_l,
                    migration_l=((ready_l, tplan_l, back_l) if overlap
                                 else None))
                return constrain_acts(h, rt, seq_shard), (new_c, st)

        xs = (params["layers"], cache,
              plan if plan is not None else _none_stack(L),
              pred if pred is not None else _none_stack(L),
              slot_weights if slot_weights is not None else _none_stack(L),
              slot_weights_back if overlap else _none_stack(L),
              slot_ready if overlap else _none_stack(L),
              target_plan if overlap else _none_stack(L),
              resched if resched is not None else _none_stack(L))
        with jax.named_scope("layers"):
            x, (new_cache, layer_stats) = jax.lax.scan(body, x, xs)
        if dense_cache is not None and new_cache is not None:
            new_cache = {**new_cache, "dense": dense_cache}
        if cfg.is_moe:
            counts, slots, aux, z, dropped, overflow = layer_stats
            stats = {"expert_counts": counts, "slot_counts": slots,
                     "aux_loss": aux.sum(), "z_loss": z.sum(),
                     "dropped": dropped,       # (L,) per-layer drop counts
                     "overflow": overflow}     # (L,) round-1 overflows
        if mode == "train":
            new_cache = None

    with jax.named_scope("lm_head"):
        if mode == "prefill":
            if last_pos is not None:
                B = x.shape[0]
                x_last = x[jnp.arange(B),
                           jnp.asarray(last_pos, jnp.int32)][:, None]
                logits = _logits(params, cfg, x_last)
            else:
                logits = _logits(params, cfg, x[:, -1:])
        else:
            logits = _logits(params, cfg, x)
    return logits, new_cache, stats


class _NoneStack:
    """Sentinel scanned alongside xs when a plan/prediction is absent."""

def _none_stack(L):
    return None
