"""Persistent per-rank replica slot-weight buffers.

The store materializes every MoE layer's slot layout as stacked
``(L, S, ...)`` weight arrays (S = ep_ranks * n_slots), sharded over the
EP mesh axis so each rank holds exactly its ``(n_slots, ...)`` block in
device memory ACROSS steps. The forward pass consumes the store through
``shard_map`` — no weight collective at all — and the
``MigrationExecutor`` refreshes only the slots a plan switch changes.

Memory: the store holds a second copy of the home experts (slots are a
superset of the home layout), i.e. ``n_slots / e_loc`` x the expert
weights per rank — the price of serving steps that never re-gather.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.placement import PlacementPlan, plan_dims
from repro.runtime import cost as _cost
from repro.runtime.diff import stacked_slot_experts


def store_sharding(mesh, ndim: int, ep_axis: str = "model"):
    """NamedSharding pinning dim 1 (the slot dim) to the EP axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(None, ep_axis, *([None] * (ndim - 2))))


@jax.jit
def _gather_slots(experts: Dict[str, jnp.ndarray], idx: jnp.ndarray):
    """{name: (L, E, ...)} x (L, S) expert ids -> {name: (L, S, ...)}."""
    return {k: jax.vmap(lambda w, s: w[s])(w, idx)
            for k, w in experts.items()}


class ReplicaStore:
    """Slot-weight buffers + host-side bookkeeping (slot map, versions)."""

    def __init__(self, weights: Dict[str, jnp.ndarray],
                 slot_experts: np.ndarray, *, num_experts: int,
                 ep_ranks: int, dup_slots: int):
        self.weights = weights                    # {name: (L, S, ...)}
        self.slot_experts = np.asarray(slot_experts)      # (L, S) host view
        self.num_experts = num_experts
        self.ep_ranks = ep_ranks
        self.dup_slots = dup_slots
        L = self.slot_experts.shape[0]
        self.version = np.zeros((L,), np.int64)   # bumped per layer on commit

    # ------------------------------------------------------------------ init
    @classmethod
    def from_params(cls, experts: Dict[str, jnp.ndarray],
                    plan_stack: PlacementPlan, *, num_experts: int,
                    ep_ranks: int, dup_slots: int, mesh=None,
                    ep_axis: str = "model") -> "ReplicaStore":
        """Build the store for a stacked plan from the stacked expert
        weights {name: (L, E, ...)}.

        Unused replica slots (no live replica points at them) are filled
        with their rank's first home expert — their contents are
        unreachable by dispatch, the fill just keeps shapes total.
        """
        e_loc, n_slots = plan_dims(num_experts, ep_ranks, dup_slots)
        se = stacked_slot_experts(plan_stack, ep_ranks, dup_slots)   # (L, S)
        rank_of_slot = np.arange(se.shape[1]) // n_slots
        fill = np.where(se >= 0, se, rank_of_slot[None, :] * e_loc)
        if mesh is None:
            weights = _gather_slots(experts, jnp.asarray(fill, jnp.int32))
            return cls(weights, se, num_experts=num_experts,
                       ep_ranks=ep_ranks, dup_slots=dup_slots)
        # Built in place, shard by shard: every rank first fills each of
        # its slots from its OWN home experts (home slots exactly; replica
        # slots whose expert lives elsewhere provisionally), then the
        # chunked migration step broadcasts the remote replicas in. No
        # device ever holds more than its (L, n_slots, ...) store shard.
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.runtime.diff import PlanDiff
        from repro.runtime.migrate import make_migrate_step, migrate_all
        blk = P(None, ep_axis)
        experts = jax.device_put(experts, NamedSharding(mesh, blk))
        local = np.where(fill // e_loc == rank_of_slot[None, :], fill,
                         rank_of_slot[None, :] * e_loc)
        build = jax.jit(jax.shard_map(
            lambda w, idx: _gather_slots(w, idx % e_loc), mesh=mesh,
            in_specs=(blk, blk), out_specs=blk, check_vma=False))
        weights = build(experts, jnp.asarray(local, jnp.int32))
        layer, slot = np.nonzero(local != fill)
        if layer.size:
            diff = PlanDiff(layer=layer.astype(np.int32),
                            dst_slot=slot.astype(np.int32),
                            src_expert=fill[layer, slot].astype(np.int32),
                            target_slot_experts=se)
            step = make_migrate_step(mesh, num_experts=num_experts,
                                     ep_ranks=ep_ranks, dup_slots=dup_slots,
                                     ep_axis=ep_axis)
            weights = migrate_all(step, weights, experts, diff)
        return cls(weights, se, num_experts=num_experts, ep_ranks=ep_ranks,
                   dup_slots=dup_slots)

    # ---------------------------------------------------------------- commit
    def adopt(self, weights: Dict[str, jnp.ndarray],
              slot_experts: np.ndarray) -> None:
        """Swap in a migrated buffer set (the double-buffer commit)."""
        changed = np.any(np.asarray(slot_experts) != self.slot_experts, axis=1)
        self.version += changed.astype(np.int64)
        self.weights = weights
        self.slot_experts = np.asarray(slot_experts)

    # ------------------------------------------------------------------ info
    @property
    def entry_bytes(self) -> int:
        return _cost.entry_bytes(self.weights)

    @property
    def hbm_bytes_per_rank(self) -> int:
        """Device memory one EP rank spends on its store shard: L layers x
        n_slots local slot entries (home second copy + replica slots) —
        the figure the ``store_hbm_budget_gb`` clamp and the roofline's
        duplication memory term account for."""
        L = int(self.slot_experts.shape[0])
        _, n_slots = plan_dims(self.num_experts, self.ep_ranks,
                               self.dup_slots)
        return L * n_slots * self.entry_bytes
