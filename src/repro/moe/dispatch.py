"""Expert-parallel MoE dispatch with placement-aware duplication.

Runs inside ``shard_map`` over the ``model`` mesh axis (EP ranks = R).
Every rank hosts ``E_loc = E/R`` home experts plus ``D`` replica slots.

Pipeline per rank (T = local tokens, S = R * n_slots global slots):

  1. (optional) resolve slot weights. With a resident
     ``repro.runtime.ReplicaStore`` shard threaded in (``slot_weights``),
     replica weights are already placed — no collective. Otherwise fill
     the replica pool per step: each source rank contributes ONE expert's
     weights; ``all_gather`` makes the pool of R candidates available
     everywhere (paper Sec 5 transfer model — that collective is the
     per-step duplication overhead the store amortizes away), skipped
     under an identity plan.
  2. route tokens (true router or an external predicted assignment).
  3. pick a replica per (token, k): round-robin over ``n_replicas[e]``.
  4. capacity-dispatch: pack tokens into a (S * C, d) send buffer —
     argsort + histogram-offset gather (``dispatch_impl="sort"``, the
     fast path) or one-hot cumsum + scatter (``"onehot"``, the reference
     oracle) — then ``all_to_all`` over the model axis.
  5. grouped expert FFN on the received (n_slots, R * C, d) block
     (pure-jnp einsum or the Pallas ``moe_gemm`` kernel).
  6. reverse ``all_to_all``; weighted combine with router gates.

Token-to-Expert predicted mode dispatches on *predicted* assignments first
(step 2 uses the prediction; overlappable with attention upstream), then
runs a second, capacity-reduced correction round for mispredicted pairs —
communication grows with the error rate exactly as the paper models.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core.placement import PlacementPlan, plan_dims
from repro.moe.router import RouterOutput


class MoEStats(NamedTuple):
    expert_counts: jnp.ndarray   # (E,) tokens routed per expert (global)
    slot_counts: jnp.ndarray     # (S,) tokens per global slot (global)
    dropped: jnp.ndarray         # scalar: tokens dropped by capacity
    aux_loss: jnp.ndarray
    z_loss: jnp.ndarray
    overflow: jnp.ndarray = 0    # scalar: round-1 capacity overflows (tokens
                                 # the reschedule rescue round tried to save;
                                 # 0 when rescheduling is off)


def capacity(t_local: int, top_k: int, num_slots_global: int, factor: float,
             multiple: int = 8) -> int:
    c = math.ceil(t_local * top_k / num_slots_global * factor)
    return max(multiple, math.ceil(c / multiple) * multiple)


def _positions_in_slot(gslot: jnp.ndarray, num_slots: int) -> jnp.ndarray:
    """Rank of each element within its slot group (one-hot cumsum trick).
    gslot: (N,) int32 in [0, num_slots). Returns (N,) int32."""
    oh = jax.nn.one_hot(gslot, num_slots, dtype=jnp.int32)      # (N, S)
    pos = jnp.cumsum(oh, axis=0) - 1
    return jnp.take_along_axis(pos, gslot[:, None], axis=1)[:, 0]


# ---------------------------------------------------------------------------
# send-buffer packing (the dispatch hot path)
#
# Both packers share one contract: assignments (token_of, gslot, valid) plus
# a per-slot capacity produce a zero-padded (num_classes * cap, d) send
# buffer, in-capacity mask, send-buffer destinations, per-slot counts and the
# dropped-token count. The drop rule is FIRST-COME within each slot in token
# order — ``_pack_sort`` relies on ``argsort`` stability to reproduce the
# one-hot oracle's decisions bit for bit.
# ---------------------------------------------------------------------------

def _pack_onehot(x, token_of, gslot, valid, *, num_classes: int, cap: int,
                 use_kernel: bool = False):
    """Reference oracle: (N, S+1) one-hot cumsum positions + scatter.

    O(N * S) work and a serialized scatter — the slowest correct
    formulation, kept as the equivalence oracle for ``_pack_sort``.
    """
    del use_kernel
    d = x.shape[1]
    g = jnp.where(valid, gslot, num_classes)        # invalid -> overflow class
    pos = _positions_in_slot(g, num_classes + 1)    # invalid don't eat capacity
    in_cap = (pos < cap) & valid
    dest = jnp.where(in_cap, g * cap + pos, num_classes * cap)
    send = jnp.zeros((num_classes * cap + 1, d), x.dtype).at[dest].set(
        x[token_of], mode="drop")[:-1]
    counts = jnp.zeros((num_classes,), jnp.int32).at[
        jnp.minimum(g, num_classes - 1)].add(in_cap.astype(jnp.int32))
    dropped = (valid & ~in_cap).sum()
    return send, in_cap, dest, counts, dropped


def _pack_sort(x, token_of, gslot, valid, *, num_classes: int, cap: int,
               use_kernel: bool = False):
    """Fast path: stable argsort + histogram-offset slot assignment.

    Positions within a slot come from a class histogram's exclusive prefix
    sum instead of an (N, S) one-hot cumsum, and the send buffer is built
    by GATHERING the sorted tokens into each slot's contiguous range
    instead of scattering — O(N log N + S*cap) and fully vectorizable.
    ``use_kernel`` routes the histogram through the Pallas kernel (TPU).
    """
    d = x.shape[1]
    N = gslot.shape[0]
    g = jnp.where(valid, gslot, num_classes)        # invalid -> overflow class
    order = jnp.argsort(g)                          # stable: token order kept
    g_sorted = g[order]
    if use_kernel:
        from repro.kernels import ops as kernel_ops
        hist, starts = kernel_ops.histogram_offsets(g, num_classes + 1)
    else:
        hist = jnp.zeros((num_classes + 1,), jnp.int32).at[g].add(1)
        starts = jnp.cumsum(hist) - hist            # exclusive prefix sum
    pos_sorted = jnp.arange(N, dtype=jnp.int32) - starts[g_sorted]
    pos = jnp.zeros((N,), jnp.int32).at[order].set(pos_sorted)
    in_cap = (pos < cap) & valid
    dest = jnp.where(in_cap, g * cap + pos, num_classes * cap)
    # slot s's send range [s*cap, s*cap + min(hist[s], cap)) gathers the
    # sorted run starting at starts[s]; the rest of the buffer stays zero.
    fill = starts[:num_classes, None] + jnp.arange(cap, dtype=jnp.int32)
    fill_ok = (jnp.arange(cap, dtype=jnp.int32)[None, :]
               < jnp.minimum(hist[:num_classes], cap)[:, None])
    tok_sorted = token_of[order]                                # (N,)
    src = tok_sorted[jnp.clip(fill, 0, N - 1)]                  # (S, cap)
    send = jnp.where(fill_ok[..., None], x[src], 0).reshape(
        num_classes * cap, d)
    counts = jnp.minimum(hist[:num_classes], cap)
    dropped = jnp.maximum(hist[:num_classes] - cap, 0).sum()
    return send, in_cap, dest, counts, dropped


_PACKERS = {"onehot": _pack_onehot, "sort": _pack_sort}


def choose_replica(plan: PlacementPlan, expert: jnp.ndarray,
                   salt: jnp.ndarray) -> jnp.ndarray:
    """Round-robin replica choice. expert, salt: (N,). Returns global slot."""
    n_rep = plan.n_replicas[expert]                              # (N,)
    choice = salt % jnp.maximum(n_rep, 1)
    return plan.replica_table[expert, jnp.minimum(choice, plan.max_copies - 1)]


# quota draw constants — must match repro.schedule.base (kept literal here so
# the dispatch hot path never imports the host-side scheduler package)
_RESCHED_Q = 1 << 16
_RESCHED_MULT = 40503        # odd -> coprime with 2^16 -> equidistributed
_RESCHED_EXPERT = 131


def choose_replica_quota(plan: PlacementPlan, quota: jnp.ndarray,
                         expert: jnp.ndarray, salt: jnp.ndarray,
                         shift: int = 0) -> jnp.ndarray:
    """Quota-weighted replica choice (the reschedule lever's routing map).

    ``quota``: (E, C_max) int32 cumulative thresholds in [0, RESCHED_Q]
    from ``repro.schedule`` (dead copy columns pinned to RESCHED_Q). A
    hashed uniform draw per (token, k) is compared against the expert's
    thresholds, so realized per-copy shares track the scheduler's quotas.
    ``shift`` rotates the choice to the expert's next copy — the rescue
    round uses ``shift=1`` to re-aim overflow tokens at an alternate slot.
    """
    u = ((salt + expert * _RESCHED_EXPERT) * _RESCHED_MULT) % _RESCHED_Q
    choice = (quota[expert] <= u[:, None]).sum(axis=1).astype(jnp.int32)
    n_rep = jnp.maximum(plan.n_replicas[expert], 1)
    choice = (choice + shift) % n_rep
    return plan.replica_table[expert, jnp.minimum(choice, plan.max_copies - 1)]


def _global_positions(gslot: jnp.ndarray, valid: jnp.ndarray,
                      num_classes: int) -> jnp.ndarray:
    """First-come position of each assignment within its global slot (same
    ordering rule as the packers, computed over ALL classes so replicated
    ranks agree on which tokens overflow). Returns (N,) int32."""
    N = gslot.shape[0]
    g = jnp.where(valid, gslot, num_classes)
    order = jnp.argsort(g)                            # stable
    hist = jnp.zeros((num_classes + 1,), jnp.int32).at[g].add(1)
    starts = jnp.cumsum(hist) - hist
    pos_sorted = jnp.arange(N, dtype=jnp.int32) - starts[g[order]]
    return jnp.zeros((N,), jnp.int32).at[order].set(pos_sorted)


def gather_replica_pool(expert_weights: dict, plan: PlacementPlan,
                        axis_name: str) -> dict:
    """Step 1: every rank contributes one expert; all_gather the pool.

    expert_weights: {name: (E_loc, ...)}. Returns {name: (R, ...)} pool.
    """
    rank = jax.lax.axis_index(axis_name)
    e_loc = next(iter(expert_weights.values())).shape[0]
    local_idx = plan.pool_expert[rank] % e_loc                  # home expert -> local
    contrib = {k: w[local_idx] for k, w in expert_weights.items()}
    return {k: jax.lax.all_gather(v, axis_name, axis=0) for k, v in contrib.items()}


def _slot_weights(expert_weights: dict, pool: Optional[dict],
                  plan: PlacementPlan, dup_slots: int, axis_name: str) -> dict:
    """Per-slot weight stack: home experts + replica slots from the pool."""
    if dup_slots == 0 or pool is None:
        return expert_weights
    rank = jax.lax.axis_index(axis_name)
    sel = plan.pool_sel[rank, :dup_slots]                       # (D,) pool entries
    out = {}
    for k, w in expert_weights.items():
        out[k] = jnp.concatenate([w, pool[k][sel]], axis=0)     # (n_slots, ...)
    return out


def _resolve_slot_weights(expert_weights: dict, slot_weights: Optional[dict],
                          plan: PlacementPlan, dup_slots: int, ranks: int,
                          axis_name: str) -> dict:
    """Per-rank (n_slots, ...) slot weights for this step.

    ``slot_weights`` (the persistent ``repro.runtime.ReplicaStore`` shard)
    wins when threaded in: replica weights are already resident, NO
    collective. Otherwise the per-step gather pool is built — skipped via
    ``lax.cond`` when the plan is the identity stack (no expert has a
    second replica), since replica-slot contents are unreachable then and
    zeros serve as well as a gathered pool.
    """
    if slot_weights is not None:
        return slot_weights
    pool = None
    if dup_slots > 0:
        def gather():
            return gather_replica_pool(expert_weights, plan, axis_name)

        def empty():
            return {k: jnp.zeros((ranks,) + w.shape[1:], w.dtype)
                    for k, w in expert_weights.items()}

        # plan arrays are replicated, so every rank takes the same branch
        pool = jax.lax.cond(jnp.any(plan.n_replicas > 1), gather, empty)
    return _slot_weights(expert_weights, pool, plan, dup_slots, axis_name)


def grouped_ffn(slot_w: dict, x: jnp.ndarray, activation: str) -> jnp.ndarray:
    """x: (n_slots, T_s, d) -> (n_slots, T_s, d). Pure-jnp grouped expert FFN
    (the Pallas `moe_gemm` kernel implements the same contraction)."""
    if activation == "swiglu":
        g = jnp.einsum("std,sdf->stf", x, slot_w["w_gate"].astype(x.dtype))
        u = jnp.einsum("std,sdf->stf", x, slot_w["w_up"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jnp.einsum("std,sdf->stf", x, slot_w["w_up"].astype(x.dtype))
        h = jax.nn.gelu(h) if activation == "gelu" else jax.nn.relu(h)
    return jnp.einsum("stf,sfd->std", h, slot_w["w_down"].astype(x.dtype))


def _dispatch_round(x, gslot, valid, *, num_slots: int, ranks: int, cap: int,
                    axis_name: str, slot_w: dict, activation: str,
                    use_kernel: bool = False, impl: str = "sort"):
    """One dispatch -> FFN -> combine round.

    x: (T, d); gslot, valid: (N,) flattened (token, k) assignments with
    token index = n // K. Returns y_flat: (N, d) per-assignment outputs
    (zeros where dropped/invalid) plus per-slot counts, drop count and the
    in-capacity mask (which the reschedule rescue round keys off).
    ``impl`` selects the send-buffer packer (see ``_PACKERS``).
    """
    T, d = x.shape
    N = gslot.shape[0]
    K = N // T
    S = ranks * num_slots
    token_of = jnp.arange(N, dtype=jnp.int32) // K

    with jax.named_scope("moe.pack"):
        send, in_cap, dest, slot_counts, dropped = _PACKERS[impl](
            x, token_of, gslot, valid, num_classes=S, cap=cap,
            use_kernel=use_kernel)
    send = send.reshape(ranks, num_slots * cap, d)
    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    # recv: (R_src, n_slots * cap, d) -> (n_slots, R_src * cap, d)
    recv = recv.reshape(ranks, num_slots, cap, d).transpose(1, 0, 2, 3) \
               .reshape(num_slots, ranks * cap, d)

    with jax.named_scope("moe.expert_ffn"):
        if use_kernel:
            from repro.kernels import ops as kernel_ops
            y_slots = kernel_ops.moe_gemm(recv, slot_w, activation)
        else:
            y_slots = grouped_ffn(slot_w, recv, activation)

    y_back = y_slots.reshape(num_slots, ranks, cap, d).transpose(1, 0, 2, 3) \
                    .reshape(ranks, num_slots * cap, d)
    y_recv = jax.lax.all_to_all(y_back, axis_name, split_axis=0, concat_axis=0,
                                tiled=False).reshape(S * cap, d)
    with jax.named_scope("moe.combine"):
        y_flat = jnp.where(in_cap[:, None],
                           y_recv[jnp.minimum(dest, S * cap - 1)], 0.0)
    return y_flat, slot_counts, dropped, in_cap


def ep_moe_ffn(
    x: jnp.ndarray,                      # (T, d) local tokens
    router_out: RouterOutput,            # from repro.moe.router.route
    expert_weights: dict,                # {w_gate/w_up/w_down: (E_loc, ...)}
    plan: PlacementPlan,
    moe: MoEConfig,
    *,
    axis_name: str,
    ep_ranks: int,
    activation: str = "swiglu",
    use_duplication: bool = True,
    predicted_idx: Optional[jnp.ndarray] = None,   # (T, K) predicted experts
    correction_cap_frac: float = 0.25,
    use_kernel: bool = False,
    slot_weights: Optional[dict] = None,  # resident per-rank (n_slots, ...) store
    resched_quota: Optional[jnp.ndarray] = None,  # (E, C_max) int32 quotas
) -> Tuple[jnp.ndarray, MoEStats]:
    """Placement-aware EP MoE FFN (see module docstring). Returns (y, stats).

    With ``resched_quota`` threaded in (the token-rescheduling lever,
    ``repro.schedule``), replica choice follows the scheduler's quotas
    instead of blind round-robin, and capacity-overflow tokens get a second
    *rescue* dispatch round aimed at an alternate copy — extra a2a bytes in
    exchange for absorbed drops, which is exactly how the GPS roofline
    costs the lever.
    """
    T, d = x.shape
    K = moe.top_k
    E = moe.num_experts
    dup_slots = moe.duplication_slots if use_duplication else 0
    e_loc, n_slots = plan_dims(E, ep_ranks, dup_slots)
    S = ep_ranks * n_slots
    cap = capacity(T, K, S, moe.capacity_factor)

    slot_w = _resolve_slot_weights(expert_weights, slot_weights, plan,
                                   dup_slots, ep_ranks, axis_name)

    true_idx = router_out.expert_idx                             # (T, K)
    gates = router_out.gates.astype(x.dtype)                     # (T, K)
    salt = (jnp.arange(T, dtype=jnp.int32)[:, None] + jnp.arange(K)[None, :])
    flat = lambda a: a.reshape(-1)

    impl = moe.dispatch_impl
    overflow = jnp.zeros((), jnp.int32)
    if predicted_idx is None:
        with jax.named_scope("moe.pack"):
            if resched_quota is None:
                gslot = choose_replica(plan, flat(true_idx), flat(salt))
            else:
                gslot = choose_replica_quota(plan, resched_quota,
                                             flat(true_idx), flat(salt))
        valid = jnp.ones((T * K,), bool)
        y_flat, slot_counts, dropped, in_cap = _dispatch_round(
            x, gslot, valid, num_slots=n_slots, ranks=ep_ranks, cap=cap,
            axis_name=axis_name, slot_w=slot_w, activation=activation,
            use_kernel=use_kernel, impl=impl)
        if resched_quota is not None:
            # --- rescue round: re-dispatch overflow to an alternate copy --
            miss = valid & ~in_cap
            overflow = miss.sum()
            cap2 = max(8, int(cap * moe.resched_cap_frac))
            gslot2 = choose_replica_quota(plan, resched_quota,
                                          flat(true_idx), flat(salt),
                                          shift=1)
            y2, slot_counts2, dropped, _ = _dispatch_round(
                x, gslot2, miss, num_slots=n_slots, ranks=ep_ranks,
                cap=cap2, axis_name=axis_name, slot_w=slot_w,
                activation=activation, use_kernel=use_kernel, impl=impl)
            y_flat = jnp.where(in_cap[:, None], y_flat, y2)
            slot_counts = slot_counts + slot_counts2
    else:
        # --- Token-to-Expert predicted mode: round 1 on predictions -------
        pred = predicted_idx.astype(jnp.int32)
        if resched_quota is None:
            pick = lambda e, s, sh: choose_replica(plan, e, s + sh)
        else:
            pick = lambda e, s, sh: choose_replica_quota(
                plan, resched_quota, e, s, shift=sh)
        gslot1 = pick(flat(pred), flat(salt), 0)
        valid1 = jnp.ones((T * K,), bool)
        y1, slot_counts, dropped1, _ = _dispatch_round(
            x, gslot1, valid1, num_slots=n_slots, ranks=ep_ranks, cap=cap,
            axis_name=axis_name, slot_w=slot_w, activation=activation,
            use_kernel=use_kernel, impl=impl)
        # --- round 2: correction for mispredicted (token, k) pairs --------
        correct = flat(pred) == flat(true_idx)
        cap2 = max(8, int(cap * correction_cap_frac))
        gslot2 = pick(flat(true_idx), flat(salt), 1)
        y2, slot_counts2, dropped2, _ = _dispatch_round(
            x, gslot2, ~correct, num_slots=n_slots, ranks=ep_ranks, cap=cap2,
            axis_name=axis_name, slot_w=slot_w, activation=activation,
            use_kernel=use_kernel, impl=impl)
        y_flat = jnp.where(correct[:, None], y1, y2)
        slot_counts = slot_counts + slot_counts2
        dropped = dropped1 + dropped2   # slight overcount: r1 drops of mispredicted pairs

    with jax.named_scope("moe.combine"):
        y = (y_flat.reshape(T, K, d) * gates[..., None]).sum(axis=1)

    counts = jnp.zeros((E,), jnp.float32).at[flat(true_idx)].add(1.0)
    stats = MoEStats(
        expert_counts=jax.lax.psum(counts, axis_name),
        slot_counts=jax.lax.psum(slot_counts, axis_name),
        dropped=jax.lax.psum(dropped, axis_name),
        aux_loss=jax.lax.pmean(router_out.aux_loss, axis_name),
        z_loss=jax.lax.pmean(router_out.z_loss, axis_name),
        overflow=jax.lax.psum(overflow, axis_name),
    )
    return y, stats


def ep_moe_ffn_replicated(
    x: jnp.ndarray,                      # (T, d) — SAME tokens on all EP ranks
    router_out: RouterOutput,
    expert_weights: dict,
    plan: PlacementPlan,
    moe: MoEConfig,
    *,
    axis_name: str,
    ep_ranks: int,
    activation: str = "swiglu",
    use_duplication: bool = True,
    predicted_idx=None,
    use_kernel: bool = False,
    tp_axis: Tuple[str, ...] = (),
    slot_weights: Optional[dict] = None,
    resched_quota: Optional[jnp.ndarray] = None,  # (E, C_max) int32 quotas
) -> Tuple[jnp.ndarray, MoEStats]:
    """Decode-path EP dispatch: tokens are replicated over the model axis
    (decode batches are too small to shard over it). Each rank computes the
    (token, k) pairs assigned to ITS slots; a psum combines results. The
    only dispatch communication is the (T * K, d) psum — appropriate for the
    latency-critical decode stage (paper Sec 2: balancing is secondary
    there, but duplication still helps the compute term).

    ``tp_axis``: 2D expert sharding for decode (EXPERIMENTS.md §Perf
    cycle 2) — expert d_ff is additionally sharded over this mesh axis, so
    weights stay fully sharded AND resident (no ZeRO re-gather per step).
    The activation is elementwise in d_ff, so each rank computes its
    f-shard's partial y and the final psum runs over (tp_axis, ep_axis)."""
    if predicted_idx is not None:
        raise NotImplementedError("predicted pre-routing is a prefill feature")
    T, d = x.shape
    K = moe.top_k
    E = moe.num_experts
    dup_slots = moe.duplication_slots if use_duplication else 0
    e_loc, n_slots = plan_dims(E, ep_ranks, dup_slots)
    S = ep_ranks * n_slots
    cap = capacity(T, K, n_slots, moe.capacity_factor)  # per-rank slot capacity

    slot_w = _resolve_slot_weights(expert_weights, slot_weights, plan,
                                   dup_slots, ep_ranks, axis_name)

    rank = jax.lax.axis_index(axis_name)
    flat = lambda a: a.reshape(-1)
    salt = (jnp.arange(T, dtype=jnp.int32)[:, None] + jnp.arange(K)[None, :])
    expert_flat = flat(router_out.expert_idx)
    token_of = jnp.arange(T * K, dtype=jnp.int32) // K

    def _local_ffn(send):
        with jax.named_scope("moe.expert_ffn"):
            xs = send.reshape(n_slots, cap, d)
            if use_kernel:
                from repro.kernels import ops as kernel_ops
                ys = kernel_ops.moe_gemm(xs, slot_w, activation)
            else:
                ys = grouped_ffn(slot_w, xs, activation)
            return ys.reshape(n_slots * cap, d)

    with jax.named_scope("moe.pack"):
        if resched_quota is None:
            gslot = choose_replica(plan, expert_flat, flat(salt))
        else:
            gslot = choose_replica_quota(plan, resched_quota, expert_flat,
                                         flat(salt))
        mine = (gslot // n_slots) == rank
        send, in_cap, dest, _, dropped = _PACKERS[moe.dispatch_impl](
            x, token_of, gslot % n_slots, mine, num_classes=n_slots, cap=cap,
            use_kernel=use_kernel)
    ys = _local_ffn(send)
    with jax.named_scope("moe.combine"):
        y_flat = jnp.where(in_cap[:, None],
                           ys[jnp.minimum(dest, n_slots * cap - 1)], 0.0)
    overflow = jnp.zeros((), jnp.int32)
    if resched_quota is not None:
        # Rescue round: every rank recomputes the GLOBAL first-come
        # positions (tokens are replicated, so all ranks agree on which
        # (token, k) pairs overflowed), then serves the subset whose
        # alternate copy lands on one of its own slots.
        pos = _global_positions(gslot, jnp.ones_like(mine), S)
        miss = pos >= cap
        overflow = miss.sum()
        gslot2 = choose_replica_quota(plan, resched_quota, expert_flat,
                                      flat(salt), shift=1)
        mine2 = ((gslot2 // n_slots) == rank) & miss
        send2, in_cap2, dest2, _, dropped = _PACKERS[moe.dispatch_impl](
            x, token_of, gslot2 % n_slots, mine2, num_classes=n_slots,
            cap=cap, use_kernel=use_kernel)
        ys2 = _local_ffn(send2)
        y2 = jnp.where(in_cap2[:, None],
                       ys2[jnp.minimum(dest2, n_slots * cap - 1)], 0.0)
        y_flat = y_flat + y2            # disjoint masks: miss vs in-cap
    # Combine per (token, k) pair BEFORE the gate-weighted sum: each pair
    # is computed on exactly one EP rank (zeros elsewhere), so the psum is
    # exact and the served output does not depend on which copy of an
    # expert took the pair — duplication never changes the tokens served.
    # tp_axis ranks hold d_ff shards: their outputs are PARTIAL sums over
    # f; one psum over (tp, ep) both combines f-partials and slot results.
    with jax.named_scope("moe.combine"):
        y_flat = jax.lax.psum(y_flat, tuple(tp_axis) + (axis_name,)
                              if tp_axis else axis_name)
        gates = router_out.gates.astype(x.dtype)
        y = (y_flat.reshape(T, K, d) * gates[..., None]).sum(axis=1)

    counts = jnp.zeros((E,), jnp.float32).at[flat(router_out.expert_idx)].add(1.0)
    slot_counts = jnp.zeros((S,), jnp.int32).at[
        jnp.minimum(gslot, S - 1)].add(in_cap.astype(jnp.int32))
    if resched_quota is not None:
        slot_counts = slot_counts.at[jnp.minimum(gslot2, S - 1)].add(
            in_cap2.astype(jnp.int32))
    stats = MoEStats(
        expert_counts=counts,                       # already global (replicated)
        slot_counts=jax.lax.psum(slot_counts, axis_name),
        dropped=jax.lax.psum(dropped, axis_name),
        aux_loss=router_out.aux_loss,
        z_loss=router_out.z_loss,
        overflow=overflow,                          # global (computed replicated)
    )
    return y, stats
