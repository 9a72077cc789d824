"""Serving engine with the paper's predict -> plan -> dispatch pipeline.

Per prediction interval (default: every batch, paper Sec 3.1):

  1. observe per-layer expert histograms from the last batches' router
     stats (the Distribution-Only predictor's input — a free side-effect
     of dispatch) and/or run the Token-to-Expert predictor on the incoming
     batch;
  2. plan: Algorithm 1 (`duplicate_experts_host`) turns the predicted
     distribution into a PlacementPlan per MoE layer;
  3. dispatch: the next prefill executes with the new plan — replicated
     experts receive their tokens round-robin, balancing per-rank load.

The engine is strategy-agnostic: ``strategy`` selects none / dist_only /
token_to_expert exactly as in the paper, and `repro.core.gps` can be asked
which one to use for the deployment's (model, hardware, skew) point.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.duplication import duplicate_experts_host
from repro.core.placement import (PlacementPlan, identity_plan,
                                  quota_limited_plan, stack_plans)
from repro.core.predictors import DistributionEstimator
from repro.models.transformer import Runtime, init_cache
from repro.obs.accuracy import PredictorAccuracyTracker
from repro.obs.trace import NULL_TRACER
from repro.serve.kvcache import (BlockAllocator, init_block_pool,
                                 pool_sharding, write_prefill_blocks)
from repro.serve.metrics import (RequestTiming, ServeMetrics, imbalance,
                                 plan_rank_loads)
from repro.serve.scheduler import (ContinuousScheduler, IterationPlan,
                                   ServeRequest)
from repro.train.steps import (make_decode_step, make_paged_decode_step,
                               make_prefill_replan_step, make_prefill_step,
                               make_slot_prefill_step)


class _nullcontext:
    def __enter__(self):
        return self
    def __exit__(self, *a):
        return False


def _clamp_store_dup_slots(cfg: ModelConfig, params, ep_ranks: int,
                           dup_slots: int) -> int:
    """Store-aware memory clamp shared by both engines: shrink the
    requested replica slots until the persistent store (a second copy of
    the home experts plus the replica slots) fits the per-rank HBM budget
    (``MoEConfig.store_hbm_budget_gb``; 0 = unlimited). Callers gate on
    store mode + a mesh — meshless engines never build a store."""
    if not (cfg.is_moe and dup_slots > 0
            and cfg.moe.replica_impl == "store"
            and cfg.moe.store_hbm_budget_gb > 0):
        return dup_slots
    from repro.core.placement import clamp_dup_slots
    from repro.runtime.cost import entry_bytes as _eb
    return clamp_dup_slots(
        cfg.moe.num_experts, ep_ranks, dup_slots,
        entry_bytes=_eb(params["layers"]["moe"]["experts"]),
        num_layers=cfg.num_layers,
        hbm_budget_bytes=cfg.moe.store_hbm_budget_gb * 1e9)


def _chunk_stall_split(moved_bytes: float, window_s: float, hw,
                       overlap: bool):
    """(hidden_s, exposed_s) of one tick's modeled wire time: overlapped
    fills hide up to one window of transfer under forward compute,
    synchronous fills expose everything."""
    from repro.runtime import cost as _c
    stall = _c.migration_stall_s(moved_bytes, hw)
    if not overlap:
        return 0.0, stall
    return _c.split_hidden_exposed(stall, window_s)


class _OverlapStoreMixin:
    """Overlapped-migration plumbing shared by ServeEngine and
    ContinuousEngine. Expects ``_store``, ``_executor``, ``_idle_ready``,
    ``cfg``, ``_current_plan()`` on the engine; engines define
    ``_overlap_active()``."""

    def _overlap_active(self) -> bool:
        raise NotImplementedError

    def _overlap_args(self):
        """(slot_weights_back, slot_ready, target_plan) threaded into the
        step fns. Idle steps pass live==back + all-False ready, so the
        jit signature (and hence the compiled program set) is identical
        whether or not a migration is in flight."""
        if self._store is None or not self._overlap_active():
            return None, None, None
        if self._executor is not None and self._executor.active:
            return (self._executor.back_weights,
                    jnp.asarray(self._executor.ready_mask()),
                    self._executor.target_plan)
        if self._idle_ready is None:
            self._idle_ready = jnp.zeros((self.cfg.num_layers,), bool)
        return self._store.weights, self._idle_ready, self._current_plan()


# ---------------------------------------------------------------------------
# XLA compile counting — the no-recompile guarantee under a mesh.
#
# ``jitted_fn._cache_size()`` is exact on a single device, but under a mesh
# the C++ fastpath may add cache entries for freshly-minted GSPMD output
# shardings WITHOUT recompiling anything. Meshed engines therefore count
# actual backend compilations through jax.monitoring instead.
# ---------------------------------------------------------------------------

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_xla_compiles = [0]
_compile_listener_installed = False


def _install_compile_listener():
    global _compile_listener_installed
    if _compile_listener_installed:
        return
    _compile_listener_installed = True
    from jax import monitoring

    def _on_event(event, duration, **kw):
        if event == _BACKEND_COMPILE_EVENT:
            _xla_compiles[0] += 1

    monitoring.register_event_duration_secs_listener(_on_event)


@dataclass
class ServeConfig:
    strategy: str = "dist_only"       # none | dist_only | token_to_expert
    predict_interval: int = 1         # batches between re-plans (paper Sec 3.1)
    dup_slots: int = 1                # replica slots per EP rank
    max_copies: int = 4               # Algorithm 1 C_max
    ema: float = 0.9                  # moving-average for the MLE estimator
    max_len: int = 2048               # KV-cache length for generation
    in_graph_replan: bool = False     # fuse Algorithm 1 into the prefill
                                      # step (no host round-trip per batch)
    migrate_chunk: int = 8            # slot entries per fixed-shape fill step
                                      # (store mode; overlap follows
                                      # MoEConfig.overlap_migration)
    # Balancing lever (combined strategy space, repro.schedule):
    #   duplicate   re-plan + migrate replica weights every interval
    #   reschedule  freeze the plan after its first adoption; rebalance by
    #               moving TOKENS across the frozen plan's copies (quota
    #               dispatch + overflow rescue round, no migration traffic)
    #   both        migrate on the interval AND token-schedule the residual
    lever: str = "duplicate"
    resched_impl: str = "greedy"      # greedy | lp (repro.schedule)


class ServeEngine(_OverlapStoreMixin):
    """Batched prefill+decode with dynamic expert duplication."""

    def __init__(self, cfg: ModelConfig, params, serve: ServeConfig,
                 mesh=None, ep_ranks: int = 1, predictor=None, tracer=None):
        self.cfg = cfg
        self.params = params
        self.serve = serve
        self.mesh = mesh
        self.ep_ranks = ep_ranks
        self.predictor = predictor            # Token-to-Expert model (optional)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.batches_seen = 0
        self._plan_stack: Optional[PlacementPlan] = None
        self.history: List[Dict] = []         # per-batch balance telemetry
        # token rescheduling (repro.schedule): quota stack traced like the
        # plan; None when the duplicate lever runs alone
        self._resched_stack = None
        self._resched_sched = None
        self._resched_frozen = False
        self._store = None                    # repro.runtime.ReplicaStore
        self._migrate_fn = None
        self._executor = None                 # LayerStagedExecutor (overlap)
        self._idle_ready = None               # cached all-False ready mask
        self._recent_step_s = 0.0             # EMA, feeds the overlap budget
        self._step_moved = False              # this call issued fill chunks
        self._window_seeded = False           # first sample (compile) skipped
        self._adopt_ticks = 0
        self._last_migration: Dict = {}

        use_dup = cfg.is_moe and serve.strategy != "none"
        dup_slots = serve.dup_slots if use_dup else 0
        if mesh is not None:
            dup_slots = _clamp_store_dup_slots(cfg, params, ep_ranks,
                                               dup_slots)
            use_dup = use_dup and dup_slots > 0
        if cfg.is_moe:
            self.moe_cfg = dataclasses.replace(
                cfg.moe, duplication_slots=dup_slots,
                max_copies=serve.max_copies)
            self.cfg = dataclasses.replace(cfg, moe=self.moe_cfg)
            self.estimator = DistributionEstimator(
                cfg.num_layers, cfg.moe.num_experts, ema=serve.ema)
            self.accuracy = PredictorAccuracyTracker(
                cfg.num_layers, cfg.moe.num_experts)
        else:
            self.moe_cfg = None
            self.estimator = None
            self.accuracy = None

        self._rt_kw = dict(mesh=mesh, ep=mesh is not None,
                           ep_ranks=ep_ranks, use_duplication=use_dup)
        self._prefill = None
        self._decode = None

    # ------------------------------------------------------------------ plan
    def _identity_stack(self) -> Optional[PlacementPlan]:
        if not self.cfg.is_moe:
            return None
        m = self.moe_cfg
        plans = [identity_plan(m.num_experts, self.ep_ranks,
                               m.duplication_slots, m.max_copies)
                 for _ in range(self.cfg.num_layers)]
        return stack_plans(plans)

    def replan(self) -> Optional[PlacementPlan]:
        """Algorithm 1 per layer from the current distribution estimate.

        Lever "reschedule" adopts ONE plan and freezes it (later re-plans
        only refresh the token-scheduler quotas — zero migration traffic);
        "both" re-plans every interval AND refreshes quotas."""
        if not self.cfg.is_moe or self.serve.strategy == "none":
            return self._identity_stack()
        m = self.moe_cfg
        if (self.serve.lever == "reschedule" and self._resched_frozen
                and self._plan_stack is not None):
            self._replan_resched()
            return self._plan_stack
        dist = self.estimator.predict()                  # (L, E)
        plans = []
        for l in range(self.cfg.num_layers):
            res = duplicate_experts_host(dist[l], self.ep_ranks,
                                         m.duplication_slots, m.max_copies)
            plans.append(res.plan)
        self._plan_stack = self._adopt_plan(stack_plans(plans))
        if self.serve.lever == "reschedule":
            self._resched_frozen = True
        self._replan_resched()
        return self._plan_stack

    def _replan_resched(self):
        """Refresh the (L, E, C_max) quota stack against the plan in force
        (see ``ContinuousEngine._replan_resched``)."""
        if (self.serve.lever == "duplicate" or not self.cfg.is_moe
                or self.serve.strategy == "none"):
            self._resched_stack = None
            return
        from repro.moe.dispatch import capacity
        from repro.schedule import make_scheduler
        m = self.moe_cfg
        plan = self._current_plan()
        if plan is None:
            self._resched_stack = None
            return
        if self._resched_sched is None:
            self._resched_sched = make_scheduler(self.serve.resched_impl)
        dist = np.asarray(self.estimator.predict(), np.float64)
        tokens = float(getattr(self, "_last_prefill_tokens", 0) or 1024)
        counts = dist * tokens * m.top_k
        t_local = max(int(tokens) // self.ep_ranks, 1)
        n_slots_g = (m.num_experts // self.ep_ranks
                     + m.duplication_slots) * self.ep_ranks
        cap = capacity(t_local, m.top_k, n_slots_g,
                       m.capacity_factor) * self.ep_ranks
        layer_plans = [jax.tree.map(lambda a, l=l: np.asarray(a)[l], plan)
                       for l in range(self.cfg.num_layers)]
        quota, results = self._resched_sched.plan_stack(
            counts, layer_plans, ep_ranks=self.ep_ranks,
            dup_slots=m.duplication_slots, cap=float(cap))
        self._resched_stack = jnp.asarray(quota)
        if self.history:
            self.history[-1]["resched_absorbed_pred"] = float(np.mean(
                [r.overflow_absorbed_frac for r in results]))
            self.history[-1]["resched_residual"] = float(np.mean(
                [r.imbalance_sched for r in results])) - 1.0

    # --------------------------------------------------------- replica store
    @property
    def _store_mode(self) -> bool:
        """Persistent slot-weight buffers instead of the per-step pool
        gather. In-graph replanning keeps the gather oracle: its plan is a
        traced value, and migration is a host decision."""
        return (self.cfg.is_moe and self.mesh is not None
                and self.moe_cfg.duplication_slots > 0
                and self.moe_cfg.replica_impl == "store"
                and not self.serve.in_graph_replan)

    @property
    def _overlap_on(self) -> bool:
        return self._store_mode and self.moe_cfg.overlap_migration

    def _slot_weights_arg(self):
        if not self._store_mode:
            return None
        if self._store is None:
            from repro.runtime import (LayerStagedExecutor, ReplicaStore,
                                       make_migrate_step)
            m = self.moe_cfg
            experts = self.params["layers"]["moe"]["experts"]
            self._store = ReplicaStore.from_params(
                experts, self._current_plan(), num_experts=m.num_experts,
                ep_ranks=self.ep_ranks, dup_slots=m.duplication_slots,
                mesh=self.mesh)
            self._migrate_fn = make_migrate_step(
                self.mesh, num_experts=m.num_experts, ep_ranks=self.ep_ranks,
                dup_slots=m.duplication_slots)
            if self._overlap_on:
                self._executor = LayerStagedExecutor(
                    self._migrate_fn, experts, self._store.entry_bytes,
                    num_layers=self.cfg.num_layers,
                    chunk=self.serve.migrate_chunk, tracer=self.tracer)
        return self._store.weights

    def _overlap_active(self) -> bool:
        return self._overlap_on

    def _hw(self):
        from repro.core.simulator import A100_PCIE
        return A100_PCIE

    def _tick_migration(self):
        """Issue this step's overlapped chunk budget (async dispatch — the
        fills queue behind / alongside the forward programs instead of
        stalling between batches); swap plan + store on commit."""
        if self._executor is None or not self._executor.active:
            return
        from repro.runtime import cost as _c
        window = self._recent_step_s
        budget = _c.overlap_chunk_budget(
            window, chunk_entries=self._executor.chunk,
            entry_bytes=self._store.entry_bytes, hw=self._hw())
        ctx = self.mesh or _nullcontext()
        with ctx:
            commit, moved = self._executor.tick(budget)
        self._adopt_ticks += 1
        if moved:
            self._step_moved = True
            hidden, exposed = _chunk_stall_split(moved, window, self._hw(),
                                                 overlap=True)
            m = self._last_migration
            m["moved_bytes"] = m.get("moved_bytes", 0.0) + moved
            m["hidden_s"] = m.get("hidden_s", 0.0) + hidden
            m["exposed_s"] = m.get("exposed_s", 0.0) + exposed
        if commit is not None:
            weights, plan, se = commit
            self._store.adopt(weights, se)
            self._plan_stack = plan
            self._last_migration["steps_to_adopt"] = self._adopt_ticks

    def _adopt_plan(self, target: PlacementPlan) -> PlacementPlan:
        """Pay weight movement once per re-plan: migrate exactly the slots
        the plan switch changes. Synchronous drain-and-swap when
        ``overlap_migration`` is off (this engine re-plans between batches
        anyway); with overlap on, a layer-staged fill is begun instead and
        rides under the following prefill/decode steps — serving reads
        old-plan slots per layer until each layer's fill commits."""
        if not self._store_mode or self._store is None:
            self.tracer.instant("plan.switch", cat="plan", track="plan",
                                args={"batch": self.batches_seen})
            return target
        from repro.runtime import migrate_all, plan_diff, plans_equal
        if (self._overlap_on and self._executor.active
                and plans_equal(self._executor.target_plan, target)):
            # the re-plan reproduced the in-flight target (stable traffic
            # quantizes to the same plan every interval): keep filling —
            # restarting would zero the cursor every batch and a diff
            # larger than one interval's budget would never commit
            return self._current_plan()
        m = self.moe_cfg
        diff = plan_diff(self._current_plan(), target, self.ep_ranks,
                         m.duplication_slots)
        moved = diff.num_entries * self._store.entry_bytes
        self._last_migration = {"entries": diff.num_entries, "bytes": moved}
        self.tracer.instant("plan.switch", cat="plan", track="plan",
                            args={"batch": self.batches_seen,
                                  "entries": int(diff.num_entries),
                                  "bytes": float(moved)})
        if diff.num_entries == 0:
            if self._executor is not None:
                self._executor.cancel()
            return target
        if self._overlap_on:
            self._executor.begin(self._store.weights, diff, target)
            self._adopt_ticks = 0
            return self._current_plan()     # old plan until commits land
        weights = migrate_all(
            self._migrate_fn, self._store.weights,
            self.params["layers"]["moe"]["experts"], diff)
        self._store.adopt(weights, diff.target_slot_experts)
        return target

    def _current_plan(self) -> Optional[PlacementPlan]:
        if self._plan_stack is None:
            self._plan_stack = self._identity_stack()
        return self._plan_stack

    def _runtime(self) -> Runtime:
        return Runtime(**self._rt_kw)

    def _steps(self):
        """Build + jit the step functions ONCE; plan/predictions are traced
        arguments so replanning never recompiles."""
        if self._prefill is None:
            rt = self._runtime()
            in_graph = (self.serve.in_graph_replan and self.cfg.is_moe
                        and self.serve.strategy == "dist_only")
            builder = (make_prefill_replan_step if in_graph
                       else make_prefill_step)
            self._prefill = jax.jit(builder(self.cfg, rt))
            self._in_graph = in_graph
            self._decode = jax.jit(make_decode_step(self.cfg, rt),
                                   static_argnums=(3,))
        return self._prefill, self._decode

    # --------------------------------------------------------------- predict
    def _predict_tokens(self, tokens: np.ndarray) -> Optional[jnp.ndarray]:
        """Token-to-Expert pre-routing: (L, B, S) -> (L, B*S, K) slots."""
        if self.serve.strategy != "token_to_expert" or self.predictor is None:
            return None
        pred = self.predictor.predict(np.asarray(tokens))          # (L, B, S)
        K = self.moe_cfg.top_k
        # top-1 prediction broadcast over k (paper predicts the top-1 expert)
        return jnp.asarray(pred)[..., None].repeat(K, -1)          # (L,B,S,K)

    # ----------------------------------------------------------------- steps
    def prefill(self, batch: Dict, cache=None):
        import time as _time
        t0 = _time.perf_counter()
        tokens = batch["tokens"]
        B, S = tokens.shape
        pred = self._predict_tokens(tokens)
        prefill_step, _ = self._steps()
        if cache is None:
            cache = init_cache(self.cfg, self._runtime(), B, self.serve.max_len)
        self._slot_weights_arg()     # materialize store + executor lazily
        self._step_moved = False
        self._tick_migration()       # overlapped fills ride this step
        # read plan AND weights only after the tick: a commit swaps both
        # atomically, and a (new plan, pre-commit weights) mix would serve
        # replica slots holding the wrong expert
        slot_w = self._slot_weights_arg()
        plan = self._current_plan()
        back_w, ready, tplan = self._overlap_args()
        self._last_prefill_tokens = B * S
        ctx = self.mesh or _nullcontext()
        with ctx:
            if getattr(self, "_in_graph", False):
                logits, cache, stats, next_plan = prefill_step(
                    self.params, batch, cache, plan, pred)
                self._plan_stack = next_plan
            else:
                logits, cache, stats = prefill_step(
                    self.params, batch, cache, plan, pred, slot_w,
                    back_w, ready, tplan, self._resched_stack)
        self._observe(stats, num_tokens=B * S,
                      skip_replan=getattr(self, "_in_graph", False))
        dt = _time.perf_counter() - t0
        self.tracer.add_span("prefill", dt,
                             ts_ns=self.tracer.now_ns() - int(dt * 1e9),
                             args={"batch": B, "tokens": B * S})
        self._note_step_time(dt)
        return logits, cache, stats

    def decode(self, tokens, cache, cache_len: int):
        _, decode_step = self._steps()
        self._slot_weights_arg()     # materialize store + executor lazily
        self._step_moved = False
        self._tick_migration()
        slot_w = self._slot_weights_arg()    # post-commit view (see prefill)
        plan = self._current_plan()
        back_w, ready, tplan = self._overlap_args()
        ctx = self.mesh or _nullcontext()
        with self.tracer.span("decode", args={"cache_len": cache_len}):
            with ctx:
                next_tok, logits, cache, stats = decode_step(
                    self.params, tokens, cache, cache_len, plan, slot_w,
                    back_w, ready, tplan, self._resched_stack)
        return next_tok, logits, cache, stats

    def _note_step_time(self, dt: float):
        """EMA of the MIGRATION-FREE prefill wall time — the overlap
        window the chunk budget is sized against. Only prefill feeds it:
        decode compiles a fresh program per static ``cache_len``, so its
        walls are compile-dominated and would inflate the window by
        orders of magnitude. Steps that issued fill chunks are excluded
        too (their wall includes the fills), and the very first sample is
        discarded (it includes the prefill compile)."""
        if self._step_moved:
            return
        if not self._window_seeded:
            self._window_seeded = True
            return
        self._recent_step_s = (dt if self._recent_step_s <= 0
                               else 0.9 * self._recent_step_s + 0.1 * dt)

    def generate(self, batch: Dict, max_new_tokens: int = 8):
        """Prefill + greedy decode; returns (generated (B, T), telemetry)."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        logits, cache, _ = self.prefill(batch, cache=None)
        next_tok = logits[:, -1].argmax(-1).astype(jnp.int32)[:, None]
        out = [next_tok]
        for t in range(max_new_tokens - 1):
            next_tok, _, cache, _ = self.decode(next_tok, cache, S + t)
            out.append(next_tok)
        return jnp.concatenate(out, axis=1), self.history[-1] if self.history else {}

    # -------------------------------------------------------------- observe
    def _observe(self, stats: Dict, num_tokens: int,
                 skip_replan: bool = False):
        """Feed router histograms to the estimator; replan on the interval."""
        self.batches_seen += 1
        if not self.cfg.is_moe or stats.get("expert_counts") is None:
            return
        counts = np.asarray(stats["expert_counts"], np.float64)   # (L, E)
        self.estimator.update(counts)
        self.accuracy.observe(counts)
        tele = {"batch": self.batches_seen,
                "skew": float(counts.sum(0).max()
                              / max(counts.sum(0).mean(), 1e-9))}
        for key in ("dropped", "overflow"):
            if stats.get(key) is not None:
                tele[key] = float(np.asarray(stats[key]).sum())
        self.history.append(tele)
        if (not skip_replan and self.serve.strategy != "none"
                and self.batches_seen % self.serve.predict_interval == 0):
            wa = self.accuracy.close_window()
            if wa is not None:
                self.tracer.counter("pred_hit_rate", wa.hit_rate,
                                    track="predictor")
                tele["pred_hit_rate"] = wa.hit_rate
                tele["pred_kl"] = wa.kl
            self.replan()
            # score the distribution this re-plan just planned from
            # against the next window's realized routing
            self.accuracy.begin_window(self.estimator.predict(),
                                       self.serve.strategy)
            if self._last_migration:
                tele["migration_entries"] = self._last_migration["entries"]
                tele["migration_bytes"] = self._last_migration["bytes"]

    # ------------------------------------------------------------- telemetry
    def rank_loads(self, slot_counts: np.ndarray) -> np.ndarray:
        """(L, S) slot counts -> (L, R) per-rank token loads."""
        m = self.moe_cfg
        n_slots = m.num_experts // self.ep_ranks + m.duplication_slots
        sc = np.asarray(slot_counts, np.float64)
        return sc.reshape(sc.shape[0], self.ep_ranks, n_slots).sum(-1)


# ===========================================================================
# continuous batching
# ===========================================================================

@dataclass
class ContinuousConfig:
    """Knobs for the continuous-batching engine.

    All shapes derived from these are STATIC: the decode batch is always
    ``max_slots``, prompts pad to ``prefill_len``, and the KV pool holds
    ``num_blocks`` blocks of ``block_size`` positions — so after warmup no
    request pattern can trigger an XLA recompile.
    """
    max_slots: int = 8                # concurrent requests / decode batch
    prefill_len: int = 64             # prompt bucket (multiple of block_size)
    block_size: int = 16              # KV positions per block
    num_blocks: int = 0               # 0 = fully provision every slot
    max_len: int = 128                # per-request prompt+generation budget
    max_prefills_per_step: int = 2    # admission rate limit per iteration
    strategy: str = "dist_only"       # initial; the controller may switch it
    predict_interval: int = 4         # iterations between re-plans
    dup_slots: int = 1                # replica slots per EP rank
    max_copies: int = 4               # Algorithm 1 C_max
    ema: float = 0.9                  # estimator moving average
    eos_id: int = -1                  # -1: generate exactly max_new_tokens
    metrics_window: int = 16          # iterations per metrics window
    # Replica-weight migration (repro.runtime; active when the engine runs
    # EP on a mesh with dup_slots > 0 and moe.replica_impl == "store")
    migrate_chunk: int = 8            # slot entries per fixed-shape step
    migrate_chunks_per_step: int = 0  # chunk steps per engine iteration
                                      # when overlap is OFF (0 = drain the
                                      # diff at replan time)
    migration_gate: bool = True       # reject re-plans whose EXPOSED stall
                                      # exceeds the predicted imbalance gain
    # Overlapped (async-prefetch) migration: None inherits
    # MoEConfig.overlap_migration. When on, the fixed chunks_per_step
    # budget is replaced by a compute-time-aware schedule (chunks sized to
    # the measured non-migration step time, runtime.cost), fills are
    # layer-staged so each layer adopts the moment its fill lands, and the
    # engine PRE-BEGINS migration toward the predicted next-window plan
    # ``prefetch_lead`` iterations before the re-plan boundary
    # (cancel-on-misprediction via MigrationExecutor.cancel).
    overlap_migration: Optional[bool] = None
    prefetch_lead: int = 2            # iterations before the boundary to
                                      # pre-begin (0 = no predictive start)
    # Balancing lever (combined strategy space, repro.schedule): initial;
    # the controller may switch it when ControllerConfig.levers offers more
    # than the duplicate lever. "reschedule" freezes the plan after its
    # first adoption and rebalances by moving TOKENS across the frozen
    # copies (quota dispatch + rescue round); "both" migrates on the
    # interval AND token-schedules the residual.
    lever: str = "duplicate"          # duplicate | reschedule | both
    resched_impl: str = "greedy"      # greedy | lp (repro.schedule)

    def __post_init__(self):
        if self.prefill_len % self.block_size:
            raise ValueError("prefill_len must be a block_size multiple")
        if self.num_blocks == 0:
            per_slot = -(-self.max_len // self.block_size)
            self.num_blocks = 1 + self.max_slots * per_slot   # +1: null block


@dataclass
class StepEvents:
    """What one engine iteration did (host-side bookkeeping for drivers)."""
    now: float
    prefilled: List[ServeRequest] = dataclasses.field(default_factory=list)
    completed: List[ServeRequest] = dataclasses.field(default_factory=list)
    preempted: List[ServeRequest] = dataclasses.field(default_factory=list)
    decoded_slots: int = 0
    decision: Optional[object] = None          # controller Decision, if any


class ContinuousEngine(_OverlapStoreMixin):
    """Continuous-batching serving engine over a paged KV block pool.

    Each ``step()`` is one mixed iteration: admit + prefill up to
    ``max_prefills_per_step`` waiting requests into free slots, then run
    ONE decode step for every running slot at its own position. Strategy
    (none / dist_only / token_to_expert) and ``predict_interval`` are
    runtime-mutable — an attached ``OnlineGPSController`` switches them as
    the observed traffic skew drifts, with zero recompilation: the
    placement plan and predictions are traced arguments, and both
    prefill signatures (with/without predictions) compile once in
    ``warmup()``.
    """

    def __init__(self, cfg: ModelConfig, params, ccfg: ContinuousConfig,
                 mesh=None, ep_ranks: int = 1, predictor=None,
                 controller=None, tracer=None, metrics=None,
                 model: str = ""):
        if cfg.family in ("ssm", "hybrid") or cfg.is_encdec:
            raise ValueError(f"{cfg.family}: continuous batching supports "
                             "uniform-stack decoder-only architectures")
        if cfg.attention != "gqa":
            raise ValueError("paged KV cache is implemented for GQA")
        if cfg.sliding_window and ccfg.prefill_len > cfg.sliding_window:
            # decode applies the window as a mask over the linear pool, but
            # prefill runs full-causal within the bucket — exact only while
            # the bucket fits inside the window
            raise ValueError(
                f"prefill_len {ccfg.prefill_len} exceeds the model's "
                f"sliding window {cfg.sliding_window}")
        self.ccfg = ccfg
        self.mesh = mesh
        self.ep_ranks = ep_ranks
        if mesh is not None:
            _install_compile_listener()
        self.predictor = predictor
        self.controller = controller
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.model = model
        self.strategy = ccfg.strategy
        self.lever = ccfg.lever
        self.predict_interval = ccfg.predict_interval
        self.iterations = 0
        self._plan_stack: Optional[PlacementPlan] = None
        # token rescheduling (repro.schedule): the quota stack is a traced
        # argument like the plan, so quota re-plans never recompile. Both
        # jit signatures (quota absent / present) compile in warmup when
        # the lever is available, so a runtime lever switch is shape-free.
        self._resched_enabled = cfg.is_moe and (
            ccfg.lever in ("reschedule", "both")
            or (controller is not None
                and any(l != "duplicate"
                        for l in getattr(controller.cfg, "levers", ()))))
        self._resched_stack = None          # (L, E, C_max) int32 device array
        self._resched_sched = None          # TokenScheduler, built lazily
        self._resched_frozen = False        # reschedule lever adopted a plan
        self._resched_residual = None       # last plan's leftover imbalance
        self._resched_absorbed_pred = None  # last plan's predicted absorption
        self._step_overflow = 0.0
        self._step_dropped = 0.0

        if cfg.is_moe:
            dup_slots = ccfg.dup_slots
            if mesh is not None:
                dup_slots = _clamp_store_dup_slots(cfg, params, ep_ranks,
                                                   dup_slots)
            self._overlap = (ccfg.overlap_migration
                             if ccfg.overlap_migration is not None
                             else cfg.moe.overlap_migration)
            # duplication slots are ALWAYS compiled in (even for strategy
            # "none", which runs the identity plan) so switching strategy
            # at runtime never changes a shape
            self.moe_cfg = dataclasses.replace(
                cfg.moe, duplication_slots=dup_slots,
                max_copies=ccfg.max_copies,
                overlap_migration=self._overlap)
            # logical duplication quota <= the compiled dup_slots: a fleet
            # arbiter moves capacity between co-resident models by moving
            # this number, never a shape (see set_dup_slot_quota)
            self.dup_slot_quota = dup_slots
            cfg = dataclasses.replace(cfg, moe=self.moe_cfg)
            self.estimator = DistributionEstimator(
                cfg.num_layers, cfg.moe.num_experts, ema=ccfg.ema)
            self.accuracy = PredictorAccuracyTracker(
                cfg.num_layers, cfg.moe.num_experts)
        else:
            self.moe_cfg = None
            self.estimator = None
            self.accuracy = None
            self._overlap = False
            self.dup_slot_quota = 0
        self.cfg = cfg
        self.params = params

        use_dup = cfg.is_moe and cfg.moe.duplication_slots > 0
        # window_override = max_len disables rotating-window caches: the
        # paged pool is linear in logical positions
        self.rt = Runtime(mesh=mesh, ep=mesh is not None, ep_ranks=ep_ranks,
                          use_duplication=use_dup,
                          window_override=ccfg.max_len)

        self.pool = init_block_pool(cfg, ccfg.num_blocks, ccfg.block_size,
                                    mesh=mesh)
        self.allocator = BlockAllocator(ccfg.num_blocks, ccfg.block_size)
        self.scheduler = ContinuousScheduler(
            ccfg.max_slots, ccfg.prefill_len, ccfg.max_len, self.allocator,
            max_prefills_per_step=ccfg.max_prefills_per_step)
        self.metrics = metrics if metrics is not None else \
            ServeMetrics(window_iters=ccfg.metrics_window)
        self._last_tokens = np.zeros((ccfg.max_slots,), np.int32)

        self._prefill_fn = jax.jit(make_slot_prefill_step(cfg, self.rt))
        self._decode_fn = jax.jit(make_paged_decode_step(cfg, self.rt))
        self._write_fn = jax.jit(write_prefill_blocks,
                                 out_shardings=pool_sharding(cfg, mesh))
        self._temp_cache = init_cache(cfg, self.rt, 1, ccfg.prefill_len)
        self._warm = False

        # ----------------------------------------------- replica-weight store
        self._store = None
        self._executor = None
        self._migrate_fn = None
        self._entry_bytes = 0
        self._recent_step_s = 0.0          # EMA over ALL steps
        # overlap window: EMA over migration-free steps, split by iteration
        # kind — prefill-bearing steps offer a much larger window than
        # decode-only ones (repro.runtime.cost.KindWindowEMA)
        from repro.runtime import KindWindowEMA
        self._serve_ema = KindWindowEMA()
        self._step_kind = "decode"
        self._step_migration_bytes = 0.0
        self._step_migration_hidden_bytes = 0.0
        self._idle_ready = None            # cached all-False ready mask
        self._adopt_ticks = 0
        self._prebegun_plan = None         # predictive pre-migration target
        self._pred_counts = None           # t2e predicted expert histogram
        if cfg.is_moe:
            from repro.runtime import cost as _mig_cost
            self._entry_bytes = _mig_cost.entry_bytes(
                params["layers"]["moe"]["experts"])
        if (cfg.is_moe and mesh is not None
                and cfg.moe.duplication_slots > 0
                and cfg.moe.replica_impl == "store"):
            from repro.runtime import (LayerStagedExecutor, MigrationExecutor,
                                       ReplicaStore, make_migrate_step)
            m = self.moe_cfg
            experts = params["layers"]["moe"]["experts"]
            self._store = ReplicaStore.from_params(
                experts, self._current_plan(), num_experts=m.num_experts,
                ep_ranks=ep_ranks, dup_slots=m.duplication_slots, mesh=mesh)
            self._migrate_fn = make_migrate_step(
                mesh, num_experts=m.num_experts, ep_ranks=ep_ranks,
                dup_slots=m.duplication_slots)
            if self._overlap:
                self._executor = LayerStagedExecutor(
                    self._migrate_fn, experts, self._store.entry_bytes,
                    num_layers=cfg.num_layers, chunk=ccfg.migrate_chunk,
                    tracer=self.tracer)
            else:
                self._executor = MigrationExecutor(
                    self._migrate_fn, experts, self._store.entry_bytes,
                    chunk=ccfg.migrate_chunk,
                    chunks_per_tick=ccfg.migrate_chunks_per_step,
                    tracer=self.tracer)

    # ------------------------------------------------------------------ plan
    def _identity_stack(self) -> Optional[PlacementPlan]:
        if not self.cfg.is_moe:
            return None
        m = self.moe_cfg
        return stack_plans([
            identity_plan(m.num_experts, self.ep_ranks, m.duplication_slots,
                          m.max_copies) for _ in range(self.cfg.num_layers)])

    def _current_plan(self) -> Optional[PlacementPlan]:
        if self._plan_stack is None:
            self._plan_stack = self._identity_stack()
        return self._plan_stack

    def replan(self):
        """Algorithm 1 per layer from the estimator's current prediction.

        Lever semantics: "duplicate" and "both" adopt a fresh plan every
        boundary (migrating changed slots); "reschedule" adopts ONE plan
        (the first boundary's, so there are replica copies to schedule
        across) and then freezes it — later boundaries only recompute the
        token-scheduler quotas, so the steady state pays zero migration
        traffic. Quotas are refreshed for any resched lever."""
        if not self.cfg.is_moe or self.strategy == "none":
            out = self._adopt_plan(self._identity_stack())
            self._resched_stack = None
            return out
        m = self.moe_cfg
        if (self.lever == "reschedule" and self._resched_frozen
                and self._plan_stack is not None):
            self._replan_resched()
            return self._plan_stack
        dist = self.estimator.predict()
        q = max(0, min(self.dup_slot_quota, m.duplication_slots))
        if q == m.duplication_slots:
            plans = [duplicate_experts_host(
                dist[l], self.ep_ranks, m.duplication_slots,
                m.max_copies).plan for l in range(self.cfg.num_layers)]
        else:
            # quota-limited: plan with only q replica slots, then rebuild
            # at the FULL compiled geometry so no traced shape changes
            plans = [quota_limited_plan(
                duplicate_experts_host(dist[l], self.ep_ranks, q,
                                       m.max_copies).assignments,
                m.num_experts, self.ep_ranks, m.duplication_slots,
                m.max_copies, quota=q) for l in range(self.cfg.num_layers)]
        out = self._adopt_plan(stack_plans(plans))
        if self.lever == "reschedule":
            self._resched_frozen = True
        self._replan_resched()
        return out

    def set_dup_slot_quota(self, quota: int) -> None:
        """Cap replica slots the planner may USE (per rank) below the
        compiled ``dup_slots``. Takes effect at the next re-plan: shrink
        strands now-unused slots (zero transfer — see
        ``runtime.diff.vacated_slots``), growth migrates weights in
        through the normal plan-diff path."""
        if self.cfg.is_moe:
            self.dup_slot_quota = max(
                0, min(int(quota), self.moe_cfg.duplication_slots))

    def _replan_resched(self):
        """Recompute the (L, E, C_max) quota stack from the estimator's
        distribution against the plan currently IN FORCE (a staged
        migration's target adopts later; the rescue round covers the
        transient). Quotas are host-side microseconds per boundary."""
        if (not self._resched_enabled or self.lever == "duplicate"
                or self.strategy == "none" or not self.cfg.is_moe):
            self._resched_stack = None
            return
        from repro.moe.dispatch import capacity
        from repro.schedule import make_scheduler
        m = self.moe_cfg
        plan = self._current_plan()
        if plan is None:
            self._resched_stack = None
            return
        if self._resched_sched is None:
            self._resched_sched = make_scheduler(self.ccfg.resched_impl)
        dist = np.asarray(self.estimator.predict(), np.float64)   # (L, E)
        # token units: the prefill bucket's routed (token, k) pairs; the
        # scheduler only needs counts and cap on the same scale
        counts = dist * float(self.ccfg.prefill_len * m.top_k)
        t_local = max(self.ccfg.prefill_len // self.ep_ranks, 1)
        n_slots_g = (m.num_experts // self.ep_ranks
                     + m.duplication_slots) * self.ep_ranks
        cap = capacity(t_local, m.top_k, n_slots_g,
                       m.capacity_factor) * self.ep_ranks
        layer_plans = [jax.tree.map(lambda a, l=l: np.asarray(a)[l], plan)
                       for l in range(self.cfg.num_layers)]
        quota, results = self._resched_sched.plan_stack(
            counts, layer_plans, ep_ranks=self.ep_ranks,
            dup_slots=m.duplication_slots, cap=float(cap))
        self._resched_stack = jnp.asarray(quota)
        self._resched_residual = float(np.mean(
            [r.imbalance_sched for r in results])) - 1.0
        self._resched_absorbed_pred = float(np.mean(
            [r.overflow_absorbed_frac for r in results]))
        self.metrics.record_resched(
            planned=True, absorbed_pred=self._resched_absorbed_pred,
            residual=self._resched_residual)
        self.tracer.instant(
            "resched.plan", cat="plan", track="plan",
            args={"iteration": self.iterations,
                  "impl": self.ccfg.resched_impl,
                  "residual": self._resched_residual,
                  "absorbed_pred": self._resched_absorbed_pred})

    # ------------------------------------------------------ replica migration
    def _hw(self):
        from repro.core.simulator import A100_PCIE
        return self.controller.cfg.hardware if self.controller else A100_PCIE

    def _overlap_window_s(self) -> float:
        """The overlap window one engine step offers a staged fill: the
        measured NON-migration step time for the CURRENT iteration kind
        (prefill-bearing vs decode-only steps differ by orders of
        magnitude, so the EMA is split per kind), falling back to the
        whole-step EMA and then to the profiled per-layer dispatch phase
        total."""
        w = self._serve_ema.window(self._step_kind)
        if w > 0:
            return w
        if self._recent_step_s > 0:
            return self._recent_step_s
        per_layer = self.metrics.phase_times.get("total", 0.0)
        return per_layer * self.cfg.num_layers

    def _overlap_budget(self) -> int:
        from repro.runtime import overlap_chunk_budget
        return overlap_chunk_budget(
            self._overlap_window_s(), chunk_entries=self.ccfg.migrate_chunk,
            entry_bytes=max(self._entry_bytes, 1), hw=self._hw())

    def _overlap_active(self) -> bool:
        return self._overlap

    def _hidden_estimate(self, stall_s: float, entries: int) -> float:
        """Predicted hidden share of a migration's stall under the overlap
        schedule: the fill drains over ``ceil(entries / (chunk * budget))``
        steps, each hiding up to one overlap window of wire time."""
        if not self._overlap or entries <= 0:
            return 0.0
        window = self._overlap_window_s()
        per_tick = max(self.ccfg.migrate_chunk * self._overlap_budget(), 1)
        drain_steps = -(-entries // per_tick)
        return min(stall_s, drain_steps * window)

    def _adopt_plan(self, target):
        """serve -> diff -> staged fill -> per-layer swap. Without a store
        the plan swaps immediately (and the diff is still costed, so
        dispatcherless smoke deployments surface the plan-churn bytes a
        real EP cluster would pay); with one, only changed slots are
        filled and each layer keeps serving the OLD plan until its fill
        commits. A pre-begun predictive migration toward this exact plan
        just keeps filling; toward a different plan it is cancelled
        (misprediction) and the fill restarts from the live buffers."""
        if (target is None or self._plan_stack is None
                or not self.cfg.is_moe
                or self.moe_cfg.duplication_slots == 0):
            self._plan_stack = target
            return target
        from repro.runtime import migration_stall_s, plan_diff, plans_equal
        m = self.moe_cfg
        if (self._executor is not None and self._executor.active
                and self._prebegun_plan is not None):
            if plans_equal(target, self._prebegun_plan):
                # prediction confirmed: the transfer started early and is
                # (partially) done — the boundary re-plan costs nothing new
                self._prebegun_plan = None
                self.metrics.record_migration(replanned=True)
                return self._plan_stack
            self._executor.cancel()
            self._prebegun_plan = None
            self.metrics.record_migration(cancelled=True)
        diff = plan_diff(self._plan_stack, target, self.ep_ranks,
                         m.duplication_slots)
        planned = diff.num_entries * self._entry_bytes
        stall = migration_stall_s(planned, self._hw())
        self.metrics.record_migration(replanned=True, planned_bytes=planned,
                                      stall_s=stall)
        self.tracer.instant(
            "plan.switch", cat="plan", track="plan",
            args={"iteration": self.iterations, "strategy": self.strategy,
                  "entries": int(diff.num_entries), "bytes": float(planned),
                  "stall_us": stall * 1e6})
        if self._store is None or diff.num_entries == 0:
            # no store to fill, or the switch moves no weights (replica
            # routing tables can shrink without any slot changing expert);
            # an in-flight migration toward an older target is superseded
            if self._executor is not None:
                self._executor.cancel()
            if self._store is None and planned > 0:
                # model the overlap economics for store-less smoke engines
                # too, so the controller sees the same hidden/exposed split
                # a real EP deployment's prefetcher would produce
                hidden = self._hidden_estimate(stall, diff.num_entries)
                self.metrics.record_migration(hidden_s=hidden,
                                              exposed_s=stall - hidden)
                self._step_migration_bytes += planned
                if stall > 0:
                    self._step_migration_hidden_bytes += \
                        planned * (hidden / stall)
            self._plan_stack = target
            return target
        if not self._migration_accept(stall, target, diff.num_entries):
            # a previously ACCEPTED in-flight fill (if any) keeps draining
            # toward its own target — it already passed the gate. A switch
            # to "none"/identity never lands here: its diff is empty, so
            # the branch above cancels any in-flight migration first.
            self.metrics.record_migration(rejected=True)
            self.tracer.instant(
                "plan.reject", cat="plan", track="plan",
                args={"iteration": self.iterations,
                      "stall_us": stall * 1e6, "bytes": float(planned)})
            return self._plan_stack
        self._executor.begin(self._store.weights, diff, target)
        self._adopt_ticks = 0
        if not self._overlap and self.ccfg.migrate_chunks_per_step == 0:
            self._tick_migration()              # drain + commit right away
        return self._plan_stack

    def _migration_accept(self, stall_s: float, target,
                          entries: int = 0) -> bool:
        """Hysteresis: a re-plan must repay its EXPOSED weight movement
        (total stall minus the share the overlap schedule hides under
        forward compute) with predicted imbalance gain before the next
        re-plan. With overlap on, re-plans whose transfer rides entirely
        under compute are accepted even when the same transfer would have
        been rejected as a synchronous stall."""
        if not self.ccfg.migration_gate or self._recent_step_s <= 0:
            return True
        from repro.runtime import should_migrate
        m = self.moe_cfg
        counts = self.estimator.predict()
        old = imbalance(plan_rank_loads(counts, self._plan_stack,
                                        self.ep_ranks, m.duplication_slots))
        new = imbalance(plan_rank_loads(counts, target, self.ep_ranks,
                                        m.duplication_slots))
        gain_frac = max(old - new, 0.0) / max(old, 1e-9)
        gain_s = gain_frac * max(self.predict_interval, 1) * self._recent_step_s
        return should_migrate(stall_s, gain_s,
                              hidden_s=self._hidden_estimate(stall_s, entries))

    def _tick_migration(self):
        """Issue this step's migration budget (compute-time-aware when
        overlapped, the fixed chunks_per_step knob otherwise); swap plan +
        store on commit. Chunk programs are enqueued WITHOUT blocking, so
        on an async backend they execute under the forward compute of the
        iteration that follows."""
        if self._executor is None or not self._executor.active:
            return
        budget = self._overlap_budget() if self._overlap else None
        with self.mesh:          # same lowering context as warmup's compile
            commit, moved = self._executor.tick(budget)
        self._adopt_ticks += 1
        if moved:
            self._step_migration_bytes += moved
            hidden, exposed = _chunk_stall_split(
                moved, self._overlap_window_s(), self._hw(),
                overlap=self._overlap)
            stall = hidden + exposed
            if stall > 0:
                self._step_migration_hidden_bytes += moved * (hidden / stall)
            self.metrics.record_migration(bytes_moved=moved, hidden_s=hidden,
                                          exposed_s=exposed)
        if commit is not None:
            weights, plan, se = commit
            self._store.adopt(weights, se)
            self._plan_stack = plan
            self._prebegun_plan = None
            self.metrics.record_migration(committed=True)

    # --------------------------------------------------------------- predict
    def _shape_predictions(self, tokens: np.ndarray):
        """(1, S) prompt -> (L, 1, S, K) predicted expert slots (the top-1
        prediction broadcast over k). One definition site: warmup and
        serving MUST build the identical jit signature."""
        pred = self.predictor.predict(np.asarray(tokens))          # (L, 1, S)
        self._last_token_pred = pred
        K = self.moe_cfg.top_k
        return jnp.asarray(pred)[..., None].repeat(K, -1)

    def _predict_tokens(self, tokens: np.ndarray):
        if self.strategy != "token_to_expert" or self.predictor is None:
            return None
        out = self._shape_predictions(tokens)
        self._note_predicted(self._last_token_pred)
        return out

    def _note_predicted(self, pred: np.ndarray):
        """Publish the Token-to-Expert predictor's output as a predicted
        next-window expert histogram — available BEFORE dispatch, so the
        prefetch controller can pre-begin migration toward the plan the
        next re-plan will most likely produce."""
        E = self.moe_cfg.num_experts
        L = self.cfg.num_layers
        ids = np.clip(np.asarray(pred).reshape(L, -1), 0, E - 1)
        hist = np.stack([np.bincount(ids[l], minlength=E)
                         for l in range(L)]).astype(np.float64)
        if self._pred_counts is None:
            self._pred_counts = hist
        else:
            e = self.ccfg.ema
            self._pred_counts = e * self._pred_counts + (1 - e) * hist

    def _predicted_dist(self) -> Optional[np.ndarray]:
        """(L, E) next-window hot-expert distribution, published EARLY:
        the Token-to-Expert predictor's aggregated output when that
        strategy runs, else the Distribution-Only estimator (whose EMA
        state is exactly what the boundary re-plan will consume)."""
        if not self.cfg.is_moe:
            return None
        if self.strategy == "token_to_expert" and self._pred_counts is not None:
            tot = np.maximum(self._pred_counts.sum(axis=1, keepdims=True),
                             1e-9)
            return self._pred_counts / tot
        return self.estimator.predict()

    def _prebegin_migration(self):
        """Start filling replica slots toward the PREDICTED next-window
        plan while the current window is still serving — by the re-plan
        boundary the transfer has ridden under ``prefetch_lead`` steps of
        forward compute. A boundary plan that differs cancels the stale
        fill (the live buffers were never touched)."""
        if self._store is None or self._executor is None:
            return
        from repro.runtime import migration_stall_s, plan_diff
        m = self.moe_cfg
        dist = self._predicted_dist()
        if dist is None:
            return
        target = stack_plans([
            duplicate_experts_host(dist[l], self.ep_ranks,
                                   m.duplication_slots, m.max_copies).plan
            for l in range(self.cfg.num_layers)])
        diff = plan_diff(self._plan_stack, target, self.ep_ranks,
                         m.duplication_slots)
        if diff.num_entries == 0:
            return
        planned = diff.num_entries * self._entry_bytes
        stall = migration_stall_s(planned, self._hw())
        if not self._migration_accept(stall, target, diff.num_entries):
            return
        self._executor.begin(self._store.weights, diff, target)
        self._prebegun_plan = target
        self._adopt_ticks = 0
        # the diff cost is accounted HERE (the boundary re-plan that
        # confirms the prediction records only the replan event, so
        # planned-vs-moved stays comparable for prebegun migrations)
        self.metrics.record_migration(prebegun=True, planned_bytes=planned,
                                      stall_s=stall)
        self.tracer.instant(
            "migration.prebegin", cat="migration", track="migration",
            args={"iteration": self.iterations,
                  "entries": int(diff.num_entries), "bytes": float(planned)})

    # ---------------------------------------------------------------- warmup
    def warmup(self):
        """Compile every step signature once (both prefill variants when a
        predictor is attached). Must run before any request is admitted —
        it writes garbage into unallocated blocks."""
        assert not self.scheduler.active_slots, "warmup() before serving"
        ccfg = self.ccfg
        toks = np.zeros((1, ccfg.prefill_len), np.int32)
        tw = np.zeros((1, ccfg.prefill_len), np.float32)
        last = jnp.zeros((1,), jnp.int32)
        plan = self._current_plan()
        table = jnp.zeros((ccfg.prefill_len // ccfg.block_size,), jnp.int32)
        preds = [None]
        if self.predictor is not None:
            preds.append(self._shape_predictions(toks))
        rescheds = [None]
        if self._resched_enabled:
            # the quota variant is its own jit signature: compile it now so
            # a runtime lever switch (controller or config) never recompiles
            from repro.schedule import even_quota_stack
            rescheds.append(jnp.asarray(even_quota_stack(
                self.cfg.num_layers, jax.tree.map(lambda a: np.asarray(a)[0],
                                                  plan))))
        slot_w = self._store.weights if self._store is not None else None
        if self._migrate_fn is not None:
            # the migration step donates its buffers, so its warmup call
            # consumes a copy — made OUTSIDE the mesh context, where
            # MigrationExecutor.begin copies during serving
            from repro.runtime.migrate import copy_buffers
            scratch = jax.block_until_ready(copy_buffers(slot_w))
        ctx = self.mesh or _nullcontext()
        with ctx:
            back_w, ready, tplan = self._overlap_args()
            if self._migrate_fn is not None:
                # compile the migration step once (a no-op chunk: every
                # entry invalid) so later plan switches never compile
                z = jnp.zeros((self.ccfg.migrate_chunk,), jnp.int32)
                jax.block_until_ready(self._migrate_fn(
                    scratch,
                    self.params["layers"]["moe"]["experts"],
                    z, z, z, jnp.zeros((self.ccfg.migrate_chunk,), bool)))
            for pred in preds:
                for resched in rescheds:
                    _, _, temp, _ = jax.block_until_ready(self._prefill_fn(
                        self.params, {"tokens": jnp.asarray(toks)},
                        self._temp_cache, plan, pred, last, jnp.asarray(tw),
                        slot_w, back_w, ready, tplan, resched))
            dec_toks = jnp.zeros((ccfg.max_slots, 1), jnp.int32)
            tables = jnp.zeros(
                (ccfg.max_slots, self.scheduler.tables.max_blocks_per_slot),
                jnp.int32)
            lens = jnp.zeros((ccfg.max_slots,), jnp.int32)
            aw = jnp.zeros((ccfg.max_slots, 1), jnp.float32)
            # run the steady-state write -> decode cycle TWICE: under a
            # mesh the pool's sharding layout settles only after the first
            # decode, and each distinct input layout is its own jit entry
            for resched in rescheds:
                for _ in range(2):
                    self.pool = jax.block_until_ready(
                        self._write_fn(self.pool, temp, table))
                    out = self._decode_fn(self.params, dec_toks, self.pool,
                                          tables, lens, plan, aw, slot_w,
                                          back_w, ready, tplan, resched)
                    self.pool = jax.block_until_ready(out[2])
            if self.mesh is not None:
                self._warm_converts()
        if self.mesh is not None:
            # the serving loop builds some device arrays OUTSIDE the mesh
            # context (jit cache keys include it) and re-plans on the host;
            # warm both so the backend-compile counter stays flat
            self._warm_converts()
            if self.cfg.is_moe and self.strategy != "none":
                self.replan()       # estimator is empty -> identity plan,
                                    # but the plan-build programs compile
                while self._executor is not None and self._executor.active:
                    self._tick_migration()      # never leak a warmup fill
                # warmup's replan must not count as serving plan churn,
                # and its garbage-token predictions must not seed the
                # prefetcher's published histogram
                self.metrics.migration = dict.fromkeys(
                    self.metrics.migration, 0.0)
                self.metrics.resched = dict.fromkeys(
                    self.metrics.resched, 0.0)
                self._pred_counts = None
        self._warm = True
        self._compile_baseline = self.compile_counts()

    def _warm_converts(self):
        """Compile the np->device conversion programs ``step()`` issues
        (their avals differ from the zeros used to warm the step fns)."""
        ccfg = self.ccfg
        t = self.scheduler.tables
        jax.block_until_ready((
            jnp.asarray([0], jnp.int32),
            jnp.asarray(t.tables[0, :ccfg.prefill_len // ccfg.block_size],
                        jnp.int32),
            jnp.asarray(self._last_tokens[:, None]),
            jnp.asarray(t.tables),
            jnp.asarray(t.lengths),
            jnp.asarray(np.zeros((ccfg.max_slots, 1), np.float32)),
            jnp.asarray(np.zeros((1, ccfg.prefill_len), np.float32)),
            jnp.asarray(np.zeros((1, ccfg.prefill_len), np.int32)),
            # the overlapped-migration ready mask (np bool (L,) -> device)
            jnp.asarray(np.zeros((self.cfg.num_layers,), bool)),
            jnp.zeros((self.cfg.num_layers,), bool),
        ) + ((
            # the np int32 quota-stack -> device conversion (re-plans build
            # quotas on the host every boundary)
            jnp.asarray(np.zeros((self.cfg.num_layers,
                                  self.moe_cfg.num_experts,
                                  self.moe_cfg.max_copies), np.int32)),
        ) if self._resched_enabled else ()))

    def compile_counts(self) -> Dict[str, int]:
        """Compilation state for the no-recompile check: per-step-function
        jit cache sizes on a single device, the process-wide backend
        compile count under a mesh (where per-fn cache sizes overcount —
        see ``_install_compile_listener``)."""
        if self.mesh is not None:
            return {"xla_compiles": _xla_compiles[0]}
        out = {}
        names = ("_prefill_fn", "_decode_fn", "_write_fn") + (
            ("_migrate_fn",) if self._migrate_fn is not None else ())
        for name in names:
            out[name] = getattr(self, name)._cache_size()
        return out

    def program_texts(self) -> Dict[str, str]:
        """Optimized HLO text of the step programs, keyed by their jit
        names (``prefill_step``, ``decode_step``, ``write_prefill_blocks``),
        at the shapes ``warmup`` compiled and in the variant ``step`` runs
        now (this plan, reschedule quota and overlap arguments, no token
        predictions). Each program is lowered and compiled again, which the
        persistent compile cache serves once warm; its instruction names
        are those of the profiler's device events, and each instruction's
        ``op_name`` metadata carries the model's named scopes. Take the
        texts after a measured window, not before it: on a TPU v5e the
        window that followed this call served 8.6% fewer steps, its
        device idle inside the decode's token pull."""
        assert self._warm, "call warmup() first"
        ccfg = self.ccfg
        S, B = ccfg.prefill_len, ccfg.max_slots
        plan = self._current_plan()
        resched = (self._resched_stack
                   if self.lever in ("reschedule", "both") else None)
        slot_w = self._store.weights if self._store is not None else None
        t = self.scheduler.tables
        with self.mesh or _nullcontext():
            back_w, ready, tplan = self._overlap_args()
            prefill = self._prefill_fn.lower(
                self.params, {"tokens": jnp.zeros((1, S), jnp.int32)},
                self._temp_cache, plan, None, jnp.zeros((1,), jnp.int32),
                jnp.zeros((1, S), jnp.float32), slot_w, back_w, ready, tplan,
                resched).compile()
            write = self._write_fn.lower(
                self.pool, prefill.out_info[2],
                jnp.zeros((S // ccfg.block_size,), jnp.int32)).compile()
            decode = self._decode_fn.lower(
                self.params, jnp.zeros((B, 1), jnp.int32), self.pool,
                jnp.asarray(t.tables), jnp.asarray(t.lengths), plan,
                jnp.zeros((B, 1), jnp.float32), slot_w, back_w, ready, tplan,
                resched).compile()
        return {"prefill_step": prefill.as_text(),
                "decode_step": decode.as_text(),
                "write_prefill_blocks": write.as_text()}

    def profile_phases(self, iters: int = 3, impl: Optional[str] = None,
                       tokens: Optional[int] = None) -> Dict[str, float]:
        """Measure the per-step phase breakdown: the paged decode
        ``attn`` kernel at this deployment's pool/table shapes (any GQA
        model, MoE or not), plus — for MoE configs — the dispatch phases
        (route/pack/a2a/ffn/combine) and the ``migrate`` chunk-fill cost
        when duplication is on. ``tokens`` picks the dispatch shape
        (default: this deployment's prefill bucket; pass ``max_slots``
        for a decode-shaped profile). The breakdown is recorded into
        ``metrics`` only when it profiles the ACTIVE ``dispatch_impl``
        and the phase columns are empty — what-if runs with an ``impl``
        override just return their numbers, and a second shape must
        ``metrics.reset_phases()`` first, so repeated calls can't
        silently double-accumulate the reported columns. Every profile
        also lands as a sequence of retrospective spans on the tracer's
        "dispatch-profile" track. Returns seconds per phase; ``migrate``
        is NOT part of ``total`` (it is paid per plan switch, not per
        step)."""
        from repro.moe.profile import (ATTN_PHASE, attn_phase_times,
                                       dispatch_phase_times,
                                       migrate_phase_time)
        m = self.moe_cfg
        tokens = tokens or self.ccfg.prefill_len
        phases: Dict[str, float] = {}
        if self.cfg.attention in ("gqa", "mixed") \
                and self.cfg.num_kv_heads > 0:
            phases.update(attn_phase_times(
                batch=self.ccfg.max_slots,
                num_kv=self.cfg.num_kv_heads,
                gqa=max(self.cfg.num_heads // self.cfg.num_kv_heads, 1),
                head_dim=self.cfg.head_dim,
                block_size=self.ccfg.block_size,
                max_blocks=max(self.ccfg.max_len // self.ccfg.block_size, 1),
                window=self.cfg.sliding_window,
                impl=getattr(self.cfg, "paged_attn_impl", "fused"),
                iters=iters))
        if m is not None:
            phases.update(dispatch_phase_times(
                d_model=self.cfg.d_model, d_ff=m.d_ff_expert,
                num_experts=m.num_experts, top_k=m.top_k,
                tokens=tokens, ranks=self.ep_ranks,
                capacity_factor=m.capacity_factor,
                impl=impl or m.dispatch_impl,
                activation=self.cfg.activation, iters=iters))
            if m.duplication_slots > 0:
                phases.update(migrate_phase_time(
                    d_model=self.cfg.d_model, d_ff=m.d_ff_expert,
                    num_experts=m.num_experts, ranks=self.ep_ranks,
                    dup_slots=m.duplication_slots,
                    layers=self.cfg.num_layers,
                    chunk=self.ccfg.migrate_chunk, iters=iters))
        if not phases:
            return {}
        ts = None
        for k in (ATTN_PHASE, "route", "pack", "a2a", "ffn", "combine",
                  "migrate"):
            if k in phases:
                ts = self.tracer.add_span(
                    k, phases[k], ts_ns=ts, cat="dispatch",
                    track="dispatch-profile",
                    args={"impl": impl or (m.dispatch_impl if m else
                                           getattr(self.cfg,
                                                   "paged_attn_impl",
                                                   "fused")),
                          "tokens": tokens})
        if (impl is None or (m is not None and impl == m.dispatch_impl)) \
                and not self.metrics.phase_times:
            self.metrics.record_phases(phases)
        return phases

    def assert_no_recompiles(self):
        assert self._warm, "call warmup() first"
        now = self.compile_counts()
        assert now == self._compile_baseline, (
            f"recompilation after warmup: {self._compile_baseline} -> {now}")

    # ------------------------------------------------------------------ step
    def submit(self, req: ServeRequest):
        self.scheduler.submit(req)
        if self.tracer.enabled:
            self.tracer.instant("request.submit", args={"rid": req.rid})

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def step(self, now: float, clock=None) -> StepEvents:
        """One mixed prefill+decode iteration starting at (virtual) time
        ``now``. ``clock``: optional zero-arg callable returning the
        CURRENT virtual time, so first-token / completion timestamps
        include the cost of the iteration that produced them (run_trace
        wires this to the scaled wall clock); default: frozen at ``now``.
        """
        import time as _time
        t_wall0 = _time.perf_counter()
        clock = clock or (lambda: now)
        ccfg = self.ccfg
        sched = self.scheduler
        events = StepEvents(now=now)
        iter_counts = None
        prefill_tokens = 0
        ctx = self.mesh or _nullcontext()
        tr = self.tracer
        on = tr.enabled                  # no span args are built when off
        step_span = tr.span("step", args=(
            {"iteration": self.iterations,
             **({"model": self.model} if self.model else {})}
            if on else None))
        step_span.__enter__()
        with tr.span("plan"):
            self._step_migration_bytes = 0.0
            self._step_migration_hidden_bytes = 0.0
            self._step_overflow = 0.0
            self._step_dropped = 0.0
            self._tick_migration()   # commit BEFORE this iteration's plan read
            plan = self._current_plan()
            resched = (self._resched_stack
                       if self.lever in ("reschedule", "both") else None)
            slot_w = self._store.weights if self._store is not None else None
            back_w, ready, tplan = self._overlap_args()

        with tr.span("admission") as adm:
            splan: IterationPlan = sched.schedule(now)
            if on:
                adm.set_args(prefills=len(splan.prefills),
                             decode_slots=len(splan.decode_slots),
                             preempted=len(splan.preempted))
        self._step_kind = "prefill" if splan.prefills else "decode"

        # ---------------------------------------------------------- prefill
        for req in splan.prefills:
            pf_span = tr.span("prefill", args=(
                {"rid": req.rid, "prompt_len": req.prompt_len}
                if on else None))
            pf_span.__enter__()
            slot = req.slot
            S = ccfg.prefill_len
            toks = np.zeros((1, S), np.int32)
            toks[0, :req.prompt_len] = req.tokens[:S]
            tw = np.zeros((1, S), np.float32)
            tw[0, :req.prompt_len] = 1.0
            pred = self._predict_tokens(toks)
            last = jnp.asarray([req.prompt_len - 1], jnp.int32)
            table = jnp.asarray(
                sched.tables.tables[slot, :S // ccfg.block_size], jnp.int32)
            with ctx:
                next_tok, _, temp, stats = self._prefill_fn(
                    self.params, {"tokens": jnp.asarray(toks)},
                    self._temp_cache, plan, pred, last, jnp.asarray(tw),
                    slot_w, back_w, ready, tplan, resched)
                self.pool = self._write_fn(self.pool, temp, table)
            with tr.span("prefill.sync"):
                tok0 = int(np.asarray(next_tok)[0, 0])
            req.generated.append(tok0)
            req.t_first_token = clock()
            self._last_tokens[slot] = tok0
            prefill_tokens += req.prompt_len
            iter_counts = self._accumulate(iter_counts, stats)
            events.prefilled.append(req)
            pf_span.__exit__()

        # ----------------------------------------------------------- finish
        # (requests whose whole budget was one token, or whose first token
        # already hit EOS, never reach decode)
        for slot in list(sched.active_slots):
            self._maybe_finish(slot, clock(), events)

        # ----------------------------------------------------------- decode
        sched.ensure_decode_capacity(splan)
        events.preempted = splan.preempted
        decode_slots = [s for s in splan.decode_slots
                        if sched.slots[s] is not None]
        attn_live = attn_alloc = 0.0
        if decode_slots:
            dec_span = tr.span("decode", args=(
                {"slots": len(decode_slots)} if on else None))
            dec_span.__enter__()
            with tr.span("decode.inputs"):
                # attention-compute roofline for this decode iteration,
                # from the PRE-increment lengths the kernel actually sees:
                # the gather oracle materializes and attends over every
                # allocated table column (max_slots x tbl_m blocks) while
                # the fused kernel's @pl.when(live) guard only computes
                # blocks holding in-context (and, under a sliding window,
                # in-window) tokens. alloc/live is the fused kernel's
                # structural speedup bound.
                bs = ccfg.block_size
                tbl_m = sched.tables.tables.shape[1]
                cl = sched.tables.lengths.astype(np.int64) + 1
                starts = np.arange(tbl_m, dtype=np.int64)[None, :] * bs
                live = starts < cl[:, None]
                if self.cfg.sliding_window > 0:
                    live &= starts + bs > cl[:, None] - self.cfg.sliding_window
                attn_live = float(live.sum())
                attn_alloc = float(ccfg.max_slots * tbl_m)
                active = np.zeros((ccfg.max_slots, 1), np.float32)
                active[decode_slots] = 1.0
                tokens_in = jnp.asarray(self._last_tokens[:, None])
                tables_in = jnp.asarray(sched.tables.tables)
                lengths_in = jnp.asarray(sched.tables.lengths)
                active_in = jnp.asarray(active)
            with tr.span("decode.launch"), ctx:
                next_tok, _, self.pool, stats = self._decode_fn(
                    self.params, tokens_in, self.pool, tables_in, lengths_in,
                    plan, active_in, slot_w, back_w, ready, tplan, resched)
            with tr.span("decode.sync"):
                nt = np.asarray(next_tok)
            with tr.span("decode.tokens"):
                for slot in decode_slots:
                    req = sched.slots[slot]
                    tok = int(nt[slot, 0])
                    req.generated.append(tok)
                    sched.tables.lengths[slot] += 1
                    self._last_tokens[slot] = tok
                iter_counts = self._accumulate(iter_counts, stats)
                events.decoded_slots = len(decode_slots)
                for slot in decode_slots:
                    self._maybe_finish(slot, clock(), events)
            dec_span.__exit__()

        # ---------------------------------------------------------- observe
        obs_span = tr.span("observe")
        obs_span.__enter__()
        self.iterations += 1
        if self.cfg.is_moe and iter_counts is not None:
            self.estimator.update(iter_counts)
            self.accuracy.observe(iter_counts)
            boundary = self.iterations % self.predict_interval == 0
            if boundary:
                # score the prediction the LAST re-plan boundary committed
                # to against the window's realized routing
                wa = self.accuracy.close_window()
                if wa is not None:
                    self.metrics.record_accuracy(wa.hit_rate, wa.kl)
                    self.tracer.counter("pred_hit_rate", wa.hit_rate,
                                        track="predictor")
                    self.tracer.counter("pred_kl", wa.kl, track="predictor")
            if self.strategy != "none" and boundary:
                self.replan()
            elif (self._overlap and self.strategy != "none"
                  and self.ccfg.prefetch_lead > 0
                  and self._executor is not None
                  and not self._executor.active
                  and self.predict_interval > self.ccfg.prefetch_lead
                  and (self.iterations + self.ccfg.prefetch_lead)
                  % self.predict_interval == 0):
                # the predictors publish next-window hot experts EARLY:
                # start moving weights toward the predicted plan now, so
                # the boundary re-plan finds the transfer already hidden
                # under this window's forward compute
                self._prebegin_migration()
            if boundary:
                self.accuracy.begin_window(
                    self._predicted_dist() if self.strategy != "none"
                    else None, self.strategy)
        if self.cfg.is_moe and (self._step_overflow or self._step_dropped):
            # rescue-round a2a surcharge: each overflow (token, k) pair is
            # re-dispatched once — activation there and back in bf16
            self.metrics.record_resched(
                overflow_tokens=self._step_overflow,
                dropped_tokens=self._step_dropped,
                extra_a2a_bytes=self._step_overflow * self.cfg.d_model * 2 * 2)
        decision = None
        if self.controller is not None and self.cfg.is_moe:
            decision = self.controller.observe(
                iter_counts, now,
                migration_bytes=self._step_migration_bytes,
                migration_hidden_bytes=self._step_migration_hidden_bytes,
                overflow_tokens=self._step_overflow,
                dropped_tokens=self._step_dropped,
                resched_residual=self._resched_residual,
                resched_absorbed_pred=self._resched_absorbed_pred)
            if decision is not None:
                self.tracer.instant(
                    "gps.decision", cat="gps", track="gps",
                    args={"recommended": decision.recommended,
                          "strategy": decision.strategy,
                          "skew": decision.skew,
                          "volatility": decision.volatility,
                          "switched": decision.switched,
                          "predict_interval": decision.predict_interval})
                self.tracer.counter("skew", decision.skew, track="gps")
                if decision.switched:
                    self.tracer.instant(
                        "gps.switch", cat="gps", track="gps",
                        args={"to": decision.strategy})
                self._apply_decision(decision)
        events.decision = decision
        obs_span.__exit__()

        with tr.span("record"):
            dt = clock() - now
            self._recent_step_s = (dt if self._recent_step_s <= 0
                                   else 0.9 * self._recent_step_s + 0.1 * dt)
            wall = _time.perf_counter() - t_wall0
            if self._step_migration_bytes == 0:
                # migration-free steps calibrate the overlap window (the
                # compute time a staged fill can hide under). Measured on
                # the WALL clock, not the caller's virtual clock — the
                # window is a physical property of the forward pass, and
                # frozen-clock callers (tests, fixed-rate replay) would
                # otherwise report 0. Keyed by iteration kind: a
                # decode-only step must not inherit a prefill-sized window
                # (and vice versa) — with the fused decode kernel the
                # decode step wall is materially smaller, so the
                # KindWindowEMA decode windows shrink to match.
                self._serve_ema.update(self._step_kind, wall)
            self.metrics.record_iteration(
                now, dt, prefill_tokens=prefill_tokens,
                decode_tokens=len(decode_slots),
                counts=iter_counts, plan=self._plan_stack,
                ep_ranks=self.ep_ranks,
                dup_slots=(self.moe_cfg.duplication_slots
                           if self.moe_cfg else 0),
                strategy=self.strategy, wall_s=wall,
                attn_live_blocks=attn_live, attn_alloc_blocks=attn_alloc)
        if on:
            step_span.set_args(prefills=len(splan.prefills),
                               decoded=len(decode_slots))
        step_span.__exit__()
        return events

    # ----------------------------------------------------------- internals
    def _accumulate(self, acc, stats):
        if not self.cfg.is_moe or stats.get("expert_counts") is None:
            return acc
        self._step_dropped += float(np.asarray(stats.get("dropped", 0)).sum())
        self._step_overflow += float(np.asarray(stats.get("overflow", 0)).sum())
        c = np.asarray(stats["expert_counts"], np.float64)
        return c if acc is None else acc + c

    def _maybe_finish(self, slot: int, now: float, events: StepEvents):
        req = self.scheduler.slots[slot]
        if req is None:
            return
        hit_eos = (self.ccfg.eos_id >= 0 and req.generated
                   and req.generated[-1] == self.ccfg.eos_id)
        if req.done or hit_eos:
            self.scheduler.finish_slot(slot, now)
            self.metrics.record_completion(RequestTiming(
                rid=req.rid, arrival=req.arrival,
                t_first_token=req.t_first_token, t_finished=now,
                prompt_len=req.prompt_len, new_tokens=len(req.generated),
                n_preemptions=req.n_preemptions, tenant=req.tenant))
            events.completed.append(req)

    def _apply_decision(self, decision):
        lever = getattr(decision, "lever", "duplicate")
        lever_changed = (self._resched_enabled and lever != self.lever
                         and decision.strategy != "none"
                         and lever in ("duplicate", "reschedule", "both"))
        if decision.strategy != self.strategy or lever_changed:
            self.strategy = decision.strategy
            if lever_changed:
                self.lever = lever
                # a fresh reschedule tenure freezes the NEXT adopted plan,
                # not whatever an older tenure froze
                self._resched_frozen = False
            # replan() handles "none" too (identity stack through
            # _adopt_plan, which also cancels any in-flight migration —
            # a direct _plan_stack write here would let a stale commit
            # reinstate the abandoned duplicated plan)
            self.replan()
        self.predict_interval = decision.predict_interval

    # ------------------------------------------------------------ trace run
    def run_trace(self, requests: List[ServeRequest], *, max_iters: int = 0,
                  time_scale: float = 1.0) -> float:
        """Replay a trace on a virtual clock: each iteration costs its
        measured wall time x ``time_scale``; idle gaps fast-forward to the
        next arrival. ``time_scale > 1`` compresses a long trace horizon
        into less wall time (every virtual second costs 1/scale wall
        seconds of compute). Returns the virtual completion time."""
        import time as _time
        for r in sorted(requests, key=lambda r: r.arrival):
            self.submit(r)
        now = 0.0
        iters = 0
        while self.has_work():
            if (not self.scheduler.active_slots and self.scheduler.waiting
                    and self.scheduler.waiting[0].arrival > now):
                now = self.scheduler.waiting[0].arrival
            t0 = _time.perf_counter()
            start = now
            self.step(start, clock=lambda: start + (
                _time.perf_counter() - t0) * time_scale)
            now = start + (_time.perf_counter() - t0) * time_scale
            iters += 1
            if max_iters and iters >= max_iters:
                break
        self.metrics.flush(
            self._plan_stack, self.ep_ranks,
            self.moe_cfg.duplication_slots if self.moe_cfg else 0)
        return now
