"""Chunked, fixed-shape replica-weight migration.

``make_migrate_step`` builds ONE jitted step that fills up to ``chunk``
changed slots: every rank contributes the entries whose source expert
lives in its home shard, a psum broadcasts them (the only collective —
bytes proportional to the chunk, not to the rank count), and each rank
scatters the entries destined for its slot block into its store shard.
The step DONATES its store buffer, so a fill updates it in place (chunk-
sized writes, never a whole-store copy per call). All shapes are static,
so a migration of any size is a sequence of identical step calls — zero
recompiles, asserted by the engines' compile-count checks.

``MigrationExecutor`` runs that sequence against a copy of the live
buffers (the double buffer: one store-sized copy per migration) under a
per-engine-step chunk budget; the engine keeps serving on the old plan +
old store until ``tick`` reports the commit payload.

``LayerStagedExecutor`` is the async-prefetch variant: it sorts the diff
by LAYER and tracks a per-layer ready-version vector. Because the forward
pass scans layers in order, a layer whose fill already completed can be
consumed from the back buffer (with the target plan row) while later
layers are still in flight — ``forward(..., slot_weights_back, slot_ready,
target_plan)`` selects per layer, so dispatch reads old-plan slots until
the fill for that layer commits and the result is bit-exact with the
synchronous path at every intermediate state.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.placement import PlacementPlan, plan_dims
from repro.obs.trace import NULL_TRACER
from repro.runtime.diff import PlanDiff


def make_migrate_step(mesh, *, num_experts: int, ep_ranks: int,
                      dup_slots: int, ep_axis: str = "model"):
    """Returns jitted ``step(weights, experts, layer, dst_slot, src_expert,
    valid) -> weights`` filling the described slots.

    weights: {name: (L, S, ...)} store buffers (sharded over ``ep_axis``
    when ``mesh`` is given), DONATED — callers keep only the returned
    buffers; experts: {name: (L, E, ...)} the home expert stacks;
    descriptor arrays: (chunk,) replicated.
    ``mesh=None`` builds the single-device variant (tests / profiling).
    """
    e_loc, n_slots = plan_dims(num_experts, ep_ranks, dup_slots)

    if mesh is None:
        def step(weights, experts, layer, dst_slot, src_expert, valid):
            out = {}
            for k, w in experts.items():
                full = w[layer, src_expert]
                li = jnp.where(valid, layer, w.shape[0])    # invalid -> drop
                out[k] = weights[k].at[li, dst_slot].set(full, mode="drop")
            return out
        return jax.jit(step, donate_argnums=0)

    from jax.sharding import PartitionSpec as P

    def inner(weights, experts, layer, dst_slot, src_expert, valid):
        rank = jax.lax.axis_index(ep_axis)
        src_rank = src_expert // e_loc
        local_e = src_expert % e_loc
        out = {}
        for k, w in experts.items():                 # w: (L, e_loc, ...)
            mask = (src_rank == rank).reshape((-1,) + (1,) * (w.ndim - 2))
            contrib = jnp.where(mask, w[layer, local_e], 0)
            full = jax.lax.psum(contrib, ep_axis)    # chunk-sized broadcast
            mine = (dst_slot // n_slots == rank) & valid
            li = jnp.where(mine, layer, w.shape[0])  # not mine -> drop
            out[k] = weights[k].at[li, dst_slot % n_slots].set(
                full, mode="drop")
        return out

    blk = P(None, ep_axis)             # prefix spec: dim 1 = slots/experts
    fn = jax.shard_map(inner, mesh=mesh,
                   in_specs=(blk, blk, P(), P(), P(), P()),
                   out_specs=blk, check_vma=False)
    return jax.jit(fn, donate_argnums=0)


def copy_buffers(weights: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Fresh device copies (same shardings) that a donating migrate step
    may consume while the originals keep serving."""
    return jax.tree.map(jnp.copy, weights)


class MigrationExecutor:
    """serve -> diff -> chunked fill -> swap state machine."""

    def __init__(self, step_fn, experts: Dict[str, jnp.ndarray],
                 entry_bytes: int, *, chunk: int = 8,
                 chunks_per_tick: int = 0, tracer=None):
        """``chunks_per_tick``: migration step calls per engine iteration
        (the per-step budget); 0 = drain the whole diff in one tick.
        ``tracer``: optional ``repro.obs.SpanTracer`` — begin/cancel/commit
        instants plus one ``migration.tick`` span per active tick land on
        a dedicated "migration" track."""
        self.step_fn = step_fn
        self.experts = experts
        self.entry_bytes = int(entry_bytes)
        self.chunk = max(int(chunk), 1)
        self.chunks_per_tick = int(chunks_per_tick)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._diff: Optional[PlanDiff] = None
        self._back: Optional[Dict[str, jnp.ndarray]] = None
        self._target_plan: Optional[PlacementPlan] = None
        self._target_se: Optional[np.ndarray] = None
        self._cursor = 0

    @property
    def active(self) -> bool:
        return self._diff is not None

    def begin(self, weights: Dict[str, jnp.ndarray], diff: PlanDiff,
              target_plan: PlacementPlan) -> None:
        """Stage a migration from the LIVE buffers toward ``target_plan``.
        Restarting while active abandons the partial back buffer (the live
        buffers were never touched, so no state is lost)."""
        self._back = None                 # release a superseded fill first
        self._back = copy_buffers(weights)
        self._diff = diff
        self._target_plan = target_plan
        self._target_se = np.asarray(diff.target_slot_experts)
        self._cursor = 0
        self.tracer.instant(
            "migration.begin", cat="migration", track="migration",
            args={"entries": int(diff.num_entries),
                  "bytes": int(diff.num_entries) * self.entry_bytes})

    def cancel(self) -> None:
        """Abandon an in-flight migration (the target plan was superseded
        by a later adoption). The live buffers were never touched."""
        if self._diff is not None:
            self.tracer.instant(
                "migration.cancel", cat="migration", track="migration",
                args={"filled_entries": int(self._cursor)})
        self._clear()

    def _clear(self) -> None:
        self._diff = self._back = self._target_plan = self._target_se = None
        self._cursor = 0

    def _run_chunk(self) -> int:
        d, c = self._diff, self._cursor
        n = min(self.chunk, d.num_entries - c)
        pad = self.chunk - n
        sl = slice(c, c + n)
        layer = jnp.asarray(np.pad(d.layer[sl], (0, pad)), jnp.int32)
        dst = jnp.asarray(np.pad(d.dst_slot[sl], (0, pad)), jnp.int32)
        src = jnp.asarray(np.pad(d.src_expert[sl], (0, pad)), jnp.int32)
        valid = jnp.asarray(np.arange(self.chunk) < n)
        self._back = self.step_fn(self._back, self.experts, layer, dst,
                                  src, valid)
        self._cursor += n
        return n

    def tick(self, budget: Optional[int] = None) -> Tuple[Optional[tuple], int]:
        """Run up to the per-step chunk budget (``budget`` overrides the
        constructor's ``chunks_per_tick`` — the overlap scheduler passes a
        compute-time-aware figure per step). Returns
        ``(commit, bytes_moved)`` — ``commit`` is
        ``(weights, target_plan, target_slot_experts)`` once the fill
        completes (the engine swaps plan + store atomically), else None."""
        if not self.active:
            return None, 0
        cap = self.chunks_per_tick if budget is None else int(budget)
        with self.tracer.span("migration.tick", cat="migration",
                              track="migration") as sp:
            moved = 0
            chunks = 0
            while self._cursor < self._diff.num_entries:
                moved += self._run_chunk()
                chunks += 1
                if cap and chunks >= cap:
                    break
            done = self._cursor >= self._diff.num_entries
            sp.set_args(chunks=chunks, moved_bytes=moved * self.entry_bytes,
                        remaining=int(self._diff.num_entries - self._cursor))
        if not done:
            return None, moved * self.entry_bytes
        commit = (self._back, self._target_plan, self._target_se)
        self.tracer.instant(
            "migration.commit", cat="migration", track="migration",
            args={"entries": int(self._diff.num_entries),
                  "bytes": int(self._diff.num_entries) * self.entry_bytes})
        self._clear()
        return commit, moved * self.entry_bytes


class LayerStagedExecutor(MigrationExecutor):
    """Layer-ordered chunked fill with a per-layer ready-version vector.

    Entries are filled in forward-scan order, so at any point the back
    buffer holds the COMPLETE target contents for a prefix of layers.
    ``ready_mask()`` reports which layers those are; the engine threads it
    (with the back buffer and target plan) into ``forward``, whose
    per-layer select adopts each layer the moment its fill lands — the
    transfer rides under the compute of the layers still being served on
    the old plan. Layers whose diff is empty are ready immediately: every
    live slot already holds the target expert, so adopting the target
    plan row there moves no weights.
    """

    def __init__(self, step_fn, experts: Dict[str, jnp.ndarray],
                 entry_bytes: int, *, num_layers: int, chunk: int = 8,
                 chunks_per_tick: int = 0, tracer=None):
        super().__init__(step_fn, experts, entry_bytes, chunk=chunk,
                         chunks_per_tick=chunks_per_tick, tracer=tracer)
        self.num_layers = int(num_layers)
        self._layer_end: Optional[np.ndarray] = None   # (L,) cum entry count

    def begin(self, weights: Dict[str, jnp.ndarray], diff: PlanDiff,
              target_plan: PlacementPlan) -> None:
        order = np.argsort(np.asarray(diff.layer), kind="stable")
        staged = PlanDiff(layer=np.asarray(diff.layer)[order],
                          dst_slot=np.asarray(diff.dst_slot)[order],
                          src_expert=np.asarray(diff.src_expert)[order],
                          target_slot_experts=diff.target_slot_experts)
        super().begin(weights, staged, target_plan)
        counts = np.bincount(staged.layer, minlength=self.num_layers)
        self._layer_end = np.cumsum(counts)

    def _clear(self) -> None:
        super()._clear()
        self._layer_end = None

    def ready_mask(self) -> np.ndarray:
        """(L,) bool: layers whose back-buffer fill is complete (safe to
        dispatch from the back buffer under the target plan). All-False
        when idle — the engine's select then reads the live pair."""
        if not self.active or self._layer_end is None:
            return np.zeros((self.num_layers,), bool)
        return self._layer_end <= self._cursor

    @property
    def back_weights(self) -> Optional[Dict[str, jnp.ndarray]]:
        """The in-flight double buffer (None when idle)."""
        return self._back

    @property
    def target_plan(self) -> Optional[PlacementPlan]:
        return self._target_plan

    @property
    def remaining_entries(self) -> int:
        if not self.active:
            return 0
        return self._diff.num_entries - self._cursor


def migrate_all(step_fn, weights: Dict[str, jnp.ndarray], experts: Dict,
                diff: PlanDiff, *, chunk: int = 8) -> Dict[str, jnp.ndarray]:
    """Synchronous helper: apply a whole diff and return the new buffers
    (the batch-engine path, where re-plans sit between batches anyway)."""
    ex = MigrationExecutor(step_fn, experts, 0, chunk=chunk)
    ex.begin(weights, diff, None)
    (new_weights, _, _), _ = ex.tick()
    return new_weights
