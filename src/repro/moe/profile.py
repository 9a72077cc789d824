"""Per-phase dispatch timing: route / pack / all_to_all / ffn / combine.

The paper's guideline (Sec 4) picks a prediction strategy from *measured*
hot-path costs, so the repo needs a way to attribute dispatch wall time to
its phases. Inside one jitted shard_map the phases can't be separated on
the host, so this module times each phase as its OWN jitted function on
representative shapes:

  route    router matmul + softmax + top-k + histogram
  pack     send-buffer construction (the ``dispatch_impl`` hot path)
  a2a      send->recv layout transform (the local cost of the all_to_all;
           the wire time is modeled by ``repro.core.simulator``)
  ffn      grouped expert FFN on the received block
  combine  per-assignment gather + gate-weighted reduction

Used by ``benchmarks/bench_dispatch`` (impl comparison) and
``ContinuousEngine.profile_phases`` (serve-side breakdown fed into
``ServeMetrics``).
"""

from __future__ import annotations

import math
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MoEConfig
from repro.moe import dispatch as dsp
from repro.moe.router import route

PHASES = ("route", "pack", "a2a", "ffn", "combine")
# Paid once per PLAN SWITCH, not per step — kept out of PHASES so per-step
# totals and the dispatch impl comparison stay impl-independent.
MIGRATE_PHASE = "migrate"
# The HOST-side cost of ISSUING one overlapped fill chunk (enqueue without
# blocking). With the async prefetcher this — not the chunk's execution —
# is what lands on the serving critical path; the execution rides under
# forward compute, so ``migrate`` must not be lumped into step time.
PREFETCH_PHASE = "prefetch"
# Paged-decode attention (not a dispatch phase — kept out of PHASES so
# ``dispatch_phase_times``' total stays a sum of dispatch work only). Timed
# per decode step at serving shapes so the fused-kernel win is visible next
# to the MoE breakdown it competes with on the step wall.
ATTN_PHASE = "attn"


def _time(fn, *args, iters: int) -> float:
    jax.block_until_ready(fn(*args))                 # compile + warm
    best = math.inf
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def dispatch_phase_times(*, d_model: int = 256, d_ff: int = 256,
                         num_experts: int = 64, top_k: int = 2,
                         tokens: int = 2048, ranks: int = 4,
                         capacity_factor: float = 1.25,
                         impl: str = "sort", activation: str = "swiglu",
                         use_kernel: bool = False, iters: int = 5,
                         seed: int = 0) -> Dict[str, float]:
    """Time each dispatch phase on a single device. Returns seconds per
    phase plus ``"total"``; ``impl`` selects the pack formulation.

    Experts map to slots identity-style (no duplication), so the phase
    shapes match an EP deployment with ``ranks`` ranks hosting
    ``num_experts / ranks`` home experts each; the all_to_all phase times
    the (ranks, n_slots, cap) layout transform that brackets the wire.
    """
    if num_experts % ranks:
        ranks = 1
    rng = np.random.default_rng(seed)
    T, K, E, d = tokens, top_k, num_experts, d_model
    N = T * K
    S = E                                  # identity plan: slot == expert
    n_slots = S // ranks
    cap = dsp.capacity(T, K, S, capacity_factor)
    moe = MoEConfig(num_experts=E, top_k=K, d_ff_expert=d_ff,
                    capacity_factor=capacity_factor, dispatch_impl=impl)

    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    router_params = {"w": jnp.asarray(rng.normal(size=(d, E)) * 0.02,
                                      jnp.float32)}
    token_of = jnp.arange(N, dtype=jnp.int32) // K
    pack = dsp._PACKERS[impl]

    # ----------------------------------------------------------- route
    route_fn = jax.jit(lambda p, t: route(
        p, moe, t, impl="fused" if use_kernel else "dense"))
    out = jax.block_until_ready(route_fn(router_params, x))
    gslot = out.expert_idx.reshape(-1)              # identity slot mapping
    gates = out.gates
    valid = jnp.ones((N,), bool)

    # ------------------------------------------------------------ pack
    pack_fn = jax.jit(lambda x_, g_: pack(
        x_, token_of, g_, valid, num_classes=S, cap=cap,
        use_kernel=use_kernel))
    send, in_cap, dest, _, _ = jax.block_until_ready(pack_fn(x, gslot))

    # ------------------------------------------------------------- a2a
    def a2a_fn(s):
        # send (S*cap, d) -> per-rank (ranks, n_slots*cap, d) -> received
        # (n_slots, ranks*cap, d): the two reshuffles around the wire
        r = s.reshape(ranks, n_slots, cap, d)
        return r.transpose(1, 0, 2, 3).reshape(n_slots, ranks * cap, d)
    a2a_jit = jax.jit(a2a_fn)
    recv = send.reshape(S, cap, d)                  # full-slot view for ffn

    # ------------------------------------------------------------- ffn
    slot_w = {
        "w_gate": jnp.asarray(rng.normal(size=(S, d, d_ff)) * 0.02, jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(S, d, d_ff)) * 0.02, jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(S, d_ff, d)) * 0.02, jnp.float32),
    }
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        ffn_fn = jax.jit(lambda r: kernel_ops.moe_gemm(r, slot_w, activation))
    else:
        ffn_fn = jax.jit(lambda r: dsp.grouped_ffn(slot_w, r, activation))
    ys = jax.block_until_ready(ffn_fn(recv)).reshape(S * cap, d)

    # --------------------------------------------------------- combine
    def combine_fn(y_recv, g):
        y_flat = jnp.where(in_cap[:, None],
                           y_recv[jnp.minimum(dest, S * cap - 1)], 0.0)
        return (y_flat.reshape(T, K, d) * g[..., None]).sum(axis=1)
    combine_jit = jax.jit(combine_fn)
    jax.block_until_ready(combine_jit(ys, gates))

    times = {
        "route": _time(route_fn, router_params, x, iters=iters),
        "pack": _time(pack_fn, x, gslot, iters=iters),
        "a2a": _time(a2a_jit, send, iters=iters),
        "ffn": _time(ffn_fn, recv, iters=iters),
        "combine": _time(combine_jit, ys, gates, iters=iters),
    }
    times["total"] = sum(times[p] for p in PHASES)
    return times


def migrate_phase_time(*, d_model: int = 256, d_ff: int = 256,
                       num_experts: int = 64, ranks: int = 4,
                       dup_slots: int = 1, layers: int = 2, chunk: int = 8,
                       iters: int = 5, seed: int = 0) -> Dict[str, float]:
    """Device-side cost of ONE fixed-shape replica-migration chunk (gather
    from the home expert stacks + masked scatter into the slot store) at
    representative shapes, plus the host-side cost of merely ISSUING that
    chunk without blocking (the ``prefetch`` phase). The wire term of a
    migration is modeled by ``repro.runtime.cost`` — ``migrate`` times the
    local work that brackets it, mirroring how the ``a2a`` phase times the
    layout transform around the dispatch collective; ``prefetch`` is the
    only part an OVERLAPPED fill charges the serving critical path (the
    execution itself rides under forward compute), so step-time accounting
    must not lump ``migrate`` into overlapped steps. Returns
    ``{"migrate": seconds, "prefetch": seconds}``."""
    from repro.core.placement import identity_plan, stack_plans
    from repro.runtime import ReplicaStore, make_migrate_step

    if num_experts % ranks:
        ranks = 1
    rng = np.random.default_rng(seed)
    E, L = num_experts, layers
    experts = {
        "w_gate": jnp.asarray(rng.normal(size=(L, E, d_model, d_ff)) * 0.02,
                              jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(L, E, d_model, d_ff)) * 0.02,
                            jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(L, E, d_ff, d_model)) * 0.02,
                              jnp.float32),
    }
    plan = stack_plans([identity_plan(E, ranks, dup_slots, 4)
                        for _ in range(L)])
    store = ReplicaStore.from_params(experts, plan, num_experts=E,
                                     ep_ranks=ranks, dup_slots=dup_slots)
    step = make_migrate_step(None, num_experts=E, ep_ranks=ranks,
                             dup_slots=dup_slots)
    n_slots = E // ranks + dup_slots
    layer = jnp.asarray(rng.integers(0, L, chunk), jnp.int32)
    dst = jnp.asarray((rng.integers(0, ranks, chunk) * n_slots
                       + E // ranks + rng.integers(0, dup_slots, chunk)),
                      jnp.int32)
    src = jnp.asarray(rng.integers(0, E, chunk), jnp.int32)
    valid = jnp.ones((chunk,), bool)
    weights = store.weights

    def fill():
        # the step donates its buffer: thread it through the calls
        nonlocal weights
        weights = step(weights, experts, layer, dst, src, valid)
        return weights

    t = _time(fill, iters=iters)
    # issue-only cost: enqueue the chunk WITHOUT waiting for its result —
    # the critical-path charge of an overlapped (async-prefetch) fill
    best_issue = math.inf
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        out = fill()
        best_issue = min(best_issue, time.perf_counter() - t0)
        jax.block_until_ready(out)       # drain before the next round
    return {MIGRATE_PHASE: t, PREFETCH_PHASE: best_issue}


def _paged_attn_inputs(*, batch: int, num_kv: int, gqa: int, head_dim: int,
                       block_size: int, max_blocks: int, valid_frac: float,
                       dtype, seed: int):
    """Representative paged-decode state: every slot allocates the full
    ``max_blocks`` table row but only ``valid_frac`` of it holds live
    tokens — the regime where the gather path's HBM traffic is fixed at
    the allocated view while the fused kernel walks only valid blocks."""
    rng = np.random.default_rng(seed)
    B, bs, K, hd, M = batch, block_size, num_kv, head_dim, max_blocks
    N = 1 + B * M                                    # block 0 = null
    q = jnp.asarray(rng.normal(size=(B, K, gqa, hd)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(N, bs, K, hd)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(N, bs, K, hd)), dtype)
    tables = jnp.asarray(
        1 + np.arange(B * M, dtype=np.int32).reshape(B, M))
    valid = max(1, int(M * bs * valid_frac))
    lengths = jnp.asarray(
        rng.integers(max(1, valid // 2), valid, size=B), jnp.int32)
    return q, k_pool, v_pool, tables, lengths


def attn_phase_times(*, batch: int = 8, num_kv: int = 8, gqa: int = 4,
                     head_dim: int = 128, block_size: int = 16,
                     max_blocks: int = 32, valid_frac: float = 0.25,
                     window: int = 0, impl: str = "fused",
                     dtype=jnp.bfloat16, iters: int = 5,
                     seed: int = 0) -> Dict[str, float]:
    """Time one paged-decode attention step at serving shapes. Returns
    ``{"attn": seconds}`` for the selected ``paged_attn_impl`` so engines
    can record it alongside the dispatch phase breakdown."""
    from repro.kernels import ops as kernel_ops
    from repro.kernels import ref as kernel_ref

    q, k_pool, v_pool, tables, lengths = _paged_attn_inputs(
        batch=batch, num_kv=num_kv, gqa=gqa, head_dim=head_dim,
        block_size=block_size, max_blocks=max_blocks,
        valid_frac=valid_frac, dtype=dtype, seed=seed)
    B, K, _, hd = q.shape
    if impl == "fused":
        fn = jax.jit(lambda q_, k_, v_: kernel_ops.paged_decode_attention(
            q_, k_, v_, tables, lengths, window=window))
    else:
        def gather(q_, k_, v_):
            k_view = k_[tables].reshape(B, -1, K, hd)
            v_view = v_[tables].reshape(B, -1, K, hd)
            return kernel_ref.paged_decode_ref(
                q_, k_view, v_view, lengths, window=window,
                block_size=block_size)
        fn = jax.jit(gather)
    return {ATTN_PHASE: _time(fn, q, k_pool, v_pool, iters=iters)}


def attn_impl_times(*, batch: int = 8, num_kv: int = 8, gqa: int = 4,
                    head_dim: int = 128, block_size: int = 16,
                    max_blocks: int = 32, valid_frac: float = 0.25,
                    window: int = 0, dtype=jnp.bfloat16, iters: int = 5,
                    seed: int = 0) -> Dict[str, float]:
    """Head-to-head paged-decode attention timing: the fused Pallas kernel
    vs the materialize-then-attend gather oracle on identical pool state,
    measured INTERLEAVED round by round (same protocol as
    ``pack_impl_times``). Returns {"fused": s, "gather": s} best-of."""
    from repro.kernels import ops as kernel_ops
    from repro.kernels import ref as kernel_ref

    q, k_pool, v_pool, tables, lengths = _paged_attn_inputs(
        batch=batch, num_kv=num_kv, gqa=gqa, head_dim=head_dim,
        block_size=block_size, max_blocks=max_blocks,
        valid_frac=valid_frac, dtype=dtype, seed=seed)
    B, K, _, hd = q.shape

    def gather(q_, k_, v_):
        k_view = k_[tables].reshape(B, -1, K, hd)
        v_view = v_[tables].reshape(B, -1, K, hd)
        return kernel_ref.paged_decode_ref(
            q_, k_view, v_view, lengths, window=window,
            block_size=block_size)

    fns = {
        "fused": jax.jit(lambda q_, k_, v_: kernel_ops.paged_decode_attention(
            q_, k_, v_, tables, lengths, window=window)),
        "gather": jax.jit(gather),
    }
    for fn in fns.values():
        jax.block_until_ready(fn(q, k_pool, v_pool))     # compile + warm
    best = {impl: math.inf for impl in fns}
    for _ in range(max(iters, 1)):
        for impl, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, k_pool, v_pool))
            best[impl] = min(best[impl], time.perf_counter() - t0)
    return best


def pack_impl_times(*, d_model: int = 256, num_experts: int = 64,
                    top_k: int = 2, tokens: int = 4096,
                    capacity_factor: float = 1.25, iters: int = 10,
                    seed: int = 0) -> Dict[str, float]:
    """Head-to-head pack-phase timing: both ``dispatch_impl`` formulations
    on identical inputs, measured INTERLEAVED round by round so machine
    drift (CPU contention, allocator state) hits both equally. Returns
    {"sort": s, "onehot": s} best-of-``iters``."""
    rng = np.random.default_rng(seed)
    T, K, E, d = tokens, top_k, num_experts, d_model
    N = T * K
    S = E
    cap = dsp.capacity(T, K, S, capacity_factor)
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    token_of = jnp.arange(N, dtype=jnp.int32) // K
    gslot = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    valid = jnp.ones((N,), bool)

    fns = {}
    for impl, pack in dsp._PACKERS.items():
        fn = jax.jit(lambda x_, g_, p=pack: p(
            x_, token_of, g_, valid, num_classes=S, cap=cap))
        jax.block_until_ready(fn(x, gslot))          # compile + warm
        fns[impl] = fn
    best = {impl: math.inf for impl in fns}
    for _ in range(max(iters, 1)):
        for impl, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(x, gslot))
            best[impl] = min(best[impl], time.perf_counter() - t0)
    return best
