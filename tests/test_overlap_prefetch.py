"""Async predicted-hot expert prefetch (overlapped plan-diff migration).

Five layers of coverage:

* LayerStagedExecutor — entries fill in layer order, the per-layer ready
  vector is monotone, layers with an empty diff are ready immediately,
  and cancel-on-misprediction leaves the live buffers untouched (a
  subsequent migration to a third plan still lands exactly);
* cost model — the compute-aware chunk budget, the hidden/exposed stall
  split, the exposed-only ``should_migrate`` gate, ``run_gps``'s
  ``migration_hidden_frac`` discount, and the controller charging only
  exposed bytes;
* store-aware memory clamp — ``clamp_dup_slots`` math, the ServeEngine
  applying it from ``MoEConfig.store_hbm_budget_gb``, and the roofline's
  duplication residency term;
* multi-device bit-exactness — at EVERY intermediate state of a staged
  migration, a forward reading (live, back, ready, target) equals the
  gather-pool oracle evaluated on the equivalent per-layer mixed plan,
  and the completed async path equals the synchronous migration;
* engine integration — a meshed ContinuousEngine with overlap on
  pre-begins migration toward the predicted plan, commits with zero
  post-warmup compiles, reports a hidden stall share, and cancels a
  mispredicted pre-begin without corrupting the store.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.duplication import duplicate_experts_host
from repro.core.placement import (clamp_dup_slots, identity_plan,
                                  stack_plans, store_bytes_per_rank)
from repro.data.synthetic import skewed_distribution
from repro.runtime import (LayerStagedExecutor, ReplicaStore,
                           make_migrate_step, migrate_all,
                           overlap_chunk_budget, plan_diff,
                           should_migrate, split_hidden_exposed,
                           stacked_slot_experts)
from tests.test_distributed import run_sub

E, R = 8, 4


def _dup_stack(layers, dup, seed=0, base_skew=2.0):
    return stack_plans([
        duplicate_experts_host(
            skewed_distribution(E, base_skew + l + seed * 0.1), R, dup, 4).plan
        for l in range(layers)])


def _identity_stack(layers, dup):
    return stack_plans([identity_plan(E, R, dup, 4) for _ in range(layers)])


def _toy_experts(layers, d=4, f=6, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w_gate": jnp.asarray(rng.normal(size=(layers, E, d, f)), jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(layers, E, d, f)), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(layers, E, f, d)), jnp.float32),
    }


# ---------------------------------------------------------------------------
# layer-staged executor
# ---------------------------------------------------------------------------

def test_staged_fill_is_layer_ordered_and_ready_monotone():
    layers, dup = 3, 2
    experts = _toy_experts(layers)
    old, new = _identity_stack(layers, dup), _dup_stack(layers, dup, seed=2)
    store = ReplicaStore.from_params(experts, old, num_experts=E,
                                     ep_ranks=R, dup_slots=dup)
    step = make_migrate_step(None, num_experts=E, ep_ranks=R, dup_slots=dup)
    diff = plan_diff(old, new, R, dup)
    assert diff.num_entries > 0
    ex = LayerStagedExecutor(step, experts, store.entry_bytes,
                             num_layers=layers, chunk=1)
    ex.begin(store.weights, diff, new)
    # entries were re-sorted by layer
    assert np.all(np.diff(ex._diff.layer) >= 0)
    se_new = stacked_slot_experts(new, R, dup)
    prev = ex.ready_mask()
    commit = None
    while commit is None:
        commit, _ = ex.tick(1)
        mask = (np.ones(layers, bool) if not ex.active
                else ex.ready_mask())
        assert np.all(mask >= prev), "ready vector must be monotone"
        if ex.active:
            # every READY layer's back buffer equals the target store
            back = ex.back_weights
            for l in np.nonzero(mask)[0]:
                live = se_new[l] >= 0
                for k, w in back.items():
                    ref = np.asarray(experts[k])[l, se_new[l][live]]
                    assert np.array_equal(np.asarray(w)[l][live], ref), (k, l)
        prev = mask
    weights, plan, se = commit
    assert np.array_equal(se, se_new)
    ref = migrate_all(step, store.weights, experts, diff, chunk=5)
    for k in weights:
        assert np.array_equal(np.asarray(weights[k]), np.asarray(ref[k])), k
    # layers whose diff is empty must be ready from the first tick
    empty_layers = np.setdiff1d(np.arange(layers), np.unique(diff.layer))
    ex.begin(store.weights, diff, new)
    if empty_layers.size:
        assert np.all(ex.ready_mask()[empty_layers])


def test_staged_cancel_then_remigrate_is_consistent():
    """Cancel mid-fill (misprediction), then migrate to a THIRD plan: the
    result equals migrating old -> third directly — no state leaked from
    the abandoned fill."""
    layers, dup = 2, 2
    experts = _toy_experts(layers)
    old = _identity_stack(layers, dup)
    wrong = _dup_stack(layers, dup, seed=4)
    right = _dup_stack(layers, dup, seed=9, base_skew=4.0)
    store = ReplicaStore.from_params(experts, old, num_experts=E,
                                     ep_ranks=R, dup_slots=dup)
    step = make_migrate_step(None, num_experts=E, ep_ranks=R, dup_slots=dup)
    ex = LayerStagedExecutor(step, experts, store.entry_bytes,
                             num_layers=layers, chunk=1)
    ex.begin(store.weights, plan_diff(old, wrong, R, dup), wrong)
    ex.tick(2)                               # partial fill toward WRONG plan
    assert ex.active
    ex.cancel()
    assert not ex.active and ex.tick() == (None, 0)
    assert not ex.ready_mask().any()
    # live buffers untouched by the abandoned fill
    ref_old = ReplicaStore.from_params(experts, old, num_experts=E,
                                       ep_ranks=R, dup_slots=dup)
    for k in store.weights:
        assert np.array_equal(np.asarray(store.weights[k]),
                              np.asarray(ref_old.weights[k])), k
    diff = plan_diff(old, right, R, dup)
    ex.begin(store.weights, diff, right)
    commit = None
    while commit is None:
        commit, _ = ex.tick(1)
    got, _, se = commit
    ref = ReplicaStore.from_params(experts, right, num_experts=E,
                                   ep_ranks=R, dup_slots=dup)
    live = stacked_slot_experts(right, R, dup) >= 0
    for k in got:
        assert np.array_equal(np.asarray(got[k])[live],
                              np.asarray(ref.weights[k])[live]), k


# ---------------------------------------------------------------------------
# cost model: budget, hidden/exposed split, GPS discount
# ---------------------------------------------------------------------------

class _HW:
    link_bw = 1e9


def test_overlap_chunk_budget_scales_with_window():
    kw = dict(chunk_entries=4, entry_bytes=int(1e6), hw=_HW)   # 4ms wire
    assert overlap_chunk_budget(0.0, **kw) == 1                # progress floor
    assert overlap_chunk_budget(0.004, **kw) == 1
    assert overlap_chunk_budget(0.040, **kw) == 10
    assert overlap_chunk_budget(1e9, **kw, max_chunks=64) == 64


def test_kind_window_ema_splits_prefill_and_decode():
    """Satellite: the overlap chunk budget must be sized against the
    iteration KIND being shadowed — one mixed EMA lets multi-ms prefill
    walls inflate the decode window by orders of magnitude."""
    from repro.runtime import KindWindowEMA
    ema = KindWindowEMA(beta=0.5)
    # decode window falls back to the only seeded kind until measured
    ema.update("prefill", 0.100)
    assert ema.window("decode") == pytest.approx(0.100)
    ema.update("decode", 0.002)
    assert ema.window("decode") == pytest.approx(0.002)
    assert ema.window("prefill") == pytest.approx(0.100)
    # each kind's EMA evolves independently of the other's samples
    ema.update("decode", 0.004)
    assert ema.window("decode") == pytest.approx(0.003)
    assert ema.window("prefill") == pytest.approx(0.100)
    assert set(ema.kinds()) == {"prefill", "decode"}


def test_continuous_engine_tracks_per_kind_windows():
    """The engine's overlap window EMA keeps separate prefill and decode
    estimates (prefill-bearing iterations must not drive the decode
    chunk budget)."""
    import jax
    from repro.configs.registry import get_config
    from repro.models.transformer import init_model
    from repro.serve import ContinuousConfig, ContinuousEngine, ServeRequest
    cfg = get_config("mixtral-8x7b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    eng = ContinuousEngine(cfg, params, ContinuousConfig(
        max_slots=2, prefill_len=16, block_size=8, max_len=32,
        strategy="dist_only"))
    eng.warmup()
    eng.run_trace([ServeRequest(rid=i, tokens=np.arange(6, dtype=np.int32),
                                max_new_tokens=4) for i in range(3)])
    kinds = eng._serve_ema.kinds()
    assert "prefill" in kinds and "decode" in kinds
    assert kinds["prefill"] > 0 and kinds["decode"] > 0


def test_split_and_gate_charge_only_exposed_stall():
    hidden, exposed = split_hidden_exposed(1.0, 0.3)
    assert hidden == pytest.approx(0.3) and exposed == pytest.approx(0.7)
    hidden, exposed = split_hidden_exposed(0.2, 5.0)
    assert hidden == pytest.approx(0.2) and exposed == 0.0
    # a stall too big to pay synchronously is accepted once mostly hidden
    assert not should_migrate(2.0, 0.5)
    assert should_migrate(2.0, 0.5, hidden_s=1.8)
    assert should_migrate(2.0, 0.0, hidden_s=99.0)


def test_run_gps_hidden_frac_discounts_duplicating_strategies():
    from repro.configs.registry import get_config
    from repro.core.gps import recommend_strategy, run_gps
    from repro.core.simulator import A100_PCIE
    cfg = get_config("mixtral-8x7b")
    base = run_gps(cfg, A100_PCIE, skew=1.8)
    stall = base.baseline.total * 10
    sync = run_gps(cfg, A100_PCIE, skew=1.8, migration_stall_s=stall)
    overlapped = run_gps(cfg, A100_PCIE, skew=1.8, migration_stall_s=stall,
                         migration_hidden_frac=1.0)
    half = run_gps(cfg, A100_PCIE, skew=1.8, migration_stall_s=stall,
                   migration_hidden_frac=0.5)
    assert overlapped.dist_only.total == pytest.approx(base.dist_only.total)
    assert (base.dist_only.total < half.dist_only.total
            < sync.dist_only.total)
    # churn that flips the verdict to "none" synchronously keeps the
    # duplicating strategy once the transfer is hidden
    name_sync, _ = recommend_strategy(cfg, A100_PCIE, skew=1.8,
                                      migration_stall_s=stall)
    name_async, _ = recommend_strategy(cfg, A100_PCIE, skew=1.8,
                                       migration_stall_s=stall,
                                       migration_hidden_frac=1.0)
    assert name_sync == "none" and name_async != "none"


def test_controller_charges_only_exposed_bytes():
    from repro.configs.registry import get_config
    from repro.serve.controller import ControllerConfig, OnlineGPSController

    def run(hidden_frac):
        ctl = OnlineGPSController(
            get_config("mixtral-8x7b"),
            ControllerConfig(window_iters=4, patience=1))
        counts = np.tile(skewed_distribution(64, 1.8) * 1000, (32, 1))
        d = None
        for i in range(4):
            d = ctl.observe(counts, float(i), migration_bytes=1e9,
                            migration_hidden_bytes=1e9 * hidden_frac)
        return d

    d_sync, d_half, d_async = run(0.0), run(0.5), run(1.0)
    assert d_sync.migration_stall_s > d_half.migration_stall_s > 0
    assert d_async.migration_stall_s == 0.0
    assert d_async.migration_hidden_frac == pytest.approx(1.0)
    assert d_half.migration_hidden_frac == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# store-aware memory clamp
# ---------------------------------------------------------------------------

def test_clamp_dup_slots_math():
    kw = dict(entry_bytes=100, num_layers=2)
    # n_slots = 2 + d -> bytes/rank = 2 * (2 + d) * 100
    assert store_bytes_per_rank(E, R, 2, **kw) == 800
    assert clamp_dup_slots(E, R, 4, hbm_budget_bytes=0, **kw) == 4
    assert clamp_dup_slots(E, R, 4, hbm_budget_bytes=1200, **kw) == 4
    assert clamp_dup_slots(E, R, 4, hbm_budget_bytes=900, **kw) == 2
    assert clamp_dup_slots(E, R, 4, hbm_budget_bytes=650, **kw) == 1
    assert clamp_dup_slots(E, R, 4, hbm_budget_bytes=100, **kw) == 0


def test_serve_engine_applies_store_hbm_budget():
    import dataclasses
    import jax
    from repro.configs.registry import get_config
    from repro.models.transformer import init_model
    from repro.runtime.cost import entry_bytes as _eb
    from repro.serve import ServeConfig, ServeEngine

    cfg = get_config("mixtral-8x7b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    entry = _eb(params["layers"]["moe"]["experts"])
    e_loc = cfg.moe.num_experts // 4
    # budget fits exactly one replica slot per rank
    budget_gb = (cfg.num_layers * (e_loc + 1) * entry) / 1e9
    clamped = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, store_hbm_budget_gb=budget_gb))
    # the clamp requires store mode, and a store requires a mesh (the
    # engine is only constructed, never stepped, so 1x1 is fine here)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = ServeEngine(clamped, params, ServeConfig(dup_slots=4),
                      mesh=mesh, ep_ranks=4)
    assert eng.moe_cfg.duplication_slots == 1
    # no budget -> untouched
    eng = ServeEngine(cfg, params, ServeConfig(dup_slots=4),
                      mesh=mesh, ep_ranks=4)
    assert eng.moe_cfg.duplication_slots == 4
    # meshless (gather fallback) engines never build a store: no clamp
    eng = ServeEngine(clamped, params, ServeConfig(dup_slots=4), ep_ranks=4)
    assert eng.moe_cfg.duplication_slots == 4


def test_roofline_counts_store_residency():
    from repro.configs.base import INPUT_SHAPES
    from repro.configs.registry import get_config
    from repro.roofline import analytic_hbm_bytes
    import dataclasses
    cfg = get_config("mixtral-8x7b")
    dup = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, duplication_slots=2))
    shape = INPUT_SHAPES["decode_32k"]
    plain = analytic_hbm_bytes(cfg, shape, chips=8)
    with_store = analytic_hbm_bytes(dup, shape, chips=8)
    ff_mult = 3
    expected = 2 * ff_mult * cfg.d_model * cfg.moe.d_ff_expert * 2 \
        * cfg.num_layers
    assert with_store - plain == pytest.approx(expected)
    # training runs the gather path (plans change under autodiff), no store
    tr = INPUT_SHAPES["train_4k"]
    assert analytic_hbm_bytes(dup, tr, chips=8) == \
        pytest.approx(analytic_hbm_bytes(cfg, tr, chips=8))


# ---------------------------------------------------------------------------
# multi-device: async path bit-exact at every intermediate state
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_overlapped_forward_bitexact_vs_gather_midstream():
    """During a staged migration the forward reading (live, back, ready,
    target) must equal the gather-pool oracle on the per-layer MIXED plan
    (ready layers -> target, others -> old) at EVERY tick, and the final
    state must equal the synchronous migration."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.core.duplication import duplicate_experts_host
        from repro.core.placement import stack_plans
        from repro.data.synthetic import skewed_distribution
        from repro.models.transformer import Runtime, forward, init_model
        from repro.runtime import (LayerStagedExecutor, ReplicaStore,
                                   make_migrate_step, migrate_all, plan_diff)

        base = get_config("mixtral-8x7b").reduced()
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, duplication_slots=2))
        E = cfg.moe.num_experts
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4, use_duplication=True)
        params = init_model(jax.random.PRNGKey(0), cfg)
        experts = params["layers"]["moe"]["experts"]
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)}
        plan_a = stack_plans([duplicate_experts_host(
            skewed_distribution(E, 2.5 + l), 4, 2, 4).plan
            for l in range(cfg.num_layers)])
        plan_b = stack_plans([duplicate_experts_host(
            skewed_distribution(E, 5.0 - l), 4, 2, 4).plan
            for l in range(cfg.num_layers)])
        store = ReplicaStore.from_params(
            experts, plan_a, num_experts=E, ep_ranks=4, dup_slots=2,
            mesh=mesh)
        mig = make_migrate_step(mesh, num_experts=E, ep_ranks=4, dup_slots=2)
        diff = plan_diff(plan_a, plan_b, 4, 2)
        assert diff.num_entries > 2, diff.num_entries

        gather_fwd = jax.jit(lambda p, b, pl: forward(
            p, cfg, b, rt, mode="train", plan=pl))
        store_fwd = jax.jit(lambda p, b, pl, sw, bw, rd, tp: forward(
            p, cfg, b, rt, mode="train", plan=pl, slot_weights=sw,
            slot_weights_back=bw, slot_ready=rd, target_plan=tp))

        ex = LayerStagedExecutor(mig, experts, store.entry_bytes,
                                 num_layers=cfg.num_layers, chunk=1)
        ex.begin(store.weights, diff, plan_b)
        states = []
        commit = None
        with mesh:
            while commit is None:
                ready = ex.ready_mask()
                # gather oracle on the equivalent per-layer mixed plan
                mixed = jax.tree.map(
                    lambda a, b_: jnp.where(
                        jnp.asarray(ready).reshape(
                            (-1,) + (1,) * (a.ndim - 1)), b_, a),
                    plan_a, plan_b)
                lg, _, sg = gather_fwd(params, batch, mixed)
                ls, _, ss = store_fwd(params, batch, plan_a, store.weights,
                                      ex.back_weights, jnp.asarray(ready),
                                      plan_b)
                states.append({
                    "ready": int(ready.sum()),
                    "diff": float(jnp.abs(lg.astype(jnp.float32)
                                          - ls.astype(jnp.float32)).max()),
                    "counts_eq": bool(jnp.array_equal(sg["expert_counts"],
                                                      ss["expert_counts"])),
                })
                commit, _ = ex.tick(1)
        weights, _, se = commit
        store.adopt(weights, se)
        sync = migrate_all(mig, ReplicaStore.from_params(
            experts, plan_a, num_experts=E, ep_ranks=4, dup_slots=2,
            mesh=mesh).weights, experts, diff, chunk=3)
        final_eq = all(bool(jnp.array_equal(store.weights[k], sync[k]))
                       for k in sync)
        print(json.dumps({"states": states, "final_eq": final_eq,
                          "L": cfg.num_layers}))
    """, timeout=1800)
    assert res["final_eq"]
    assert len(res["states"]) >= 3
    partial = [s for s in res["states"] if 0 < s["ready"] < res["L"]]
    assert partial, "no intermediate mixed state was exercised"
    for s in res["states"]:
        assert s["diff"] == 0.0, s
        assert s["counts_eq"], s


@pytest.mark.slow
def test_serve_engine_generate_tokens_equal_overlap_on_off():
    """Greedy generation through a meshed ServeEngine (re-plans every
    batch, staged migrations in flight) produces IDENTICAL token ids with
    overlap on and off — catches any (plan, store) tear, e.g. reading
    pre-commit weights under a post-commit plan."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import init_model
        from repro.serve import ServeConfig, ServeEngine

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("mixtral-8x7b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        outs = {}
        for overlap in (True, False):
            c = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, overlap_migration=overlap))
            eng = ServeEngine(c, params,
                              ServeConfig(strategy="dist_only", dup_slots=1,
                                          max_len=64),
                              mesh=mesh, ep_ranks=4)
            rng = np.random.default_rng(0)
            toks = []
            for b in range(3):
                batch = {"tokens": jnp.asarray(
                    rng.integers(0, c.vocab_size // 4, (2, 16)))}
                gen, _ = eng.generate(batch, max_new_tokens=6)
                toks.append(np.asarray(gen))
            outs[overlap] = np.concatenate(toks)
        print(json.dumps({"equal": bool(np.array_equal(outs[True],
                                                       outs[False]))}))
    """, timeout=1800)
    assert res["equal"]


@pytest.mark.slow
def test_meshed_engine_prefetch_overlap_no_recompiles():
    """Meshed ContinuousEngine, overlap on: pre-begins migration toward
    the predicted plan before the boundary, commits, reports hidden
    stall, cancels a forced misprediction cleanly — zero XLA compiles
    after warmup throughout."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import init_model
        from repro.runtime import stacked_slot_experts
        from repro.serve import ContinuousConfig, ContinuousEngine
        from repro.serve.scheduler import ServeRequest

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("mixtral-8x7b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        ccfg = ContinuousConfig(max_slots=4, prefill_len=32, block_size=16,
                                max_len=48, strategy="dist_only",
                                predict_interval=4, dup_slots=1,
                                metrics_window=4, overlap_migration=True,
                                prefetch_lead=2, migration_gate=False)
        eng = ContinuousEngine(cfg, params, ccfg, mesh=mesh, ep_ranks=4)
        assert eng._overlap and eng._executor is not None
        eng.warmup()
        rng = np.random.default_rng(0)
        # skewed prompts so re-plans actually duplicate experts
        for i in range(8):
            eng.submit(ServeRequest(
                rid=i, arrival=0.0,
                tokens=rng.integers(0, cfg.vocab_size // 8, 16).tolist(),
                max_new_tokens=6))
        n = 0
        while eng.has_work() and n < 60:
            eng.step(float(n)); n += 1
        # force a misprediction: settle on the identity plan, pre-begin
        # toward the (duplicated) predicted plan, then adopt a DIFFERENT
        # plan at the boundary -> the stale fill must be cancelled
        eng._adopt_plan(eng._identity_stack())
        while eng._executor.active:
            eng._tick_migration()
        eng._prebegin_migration()
        assert eng._executor.active, "pre-begin produced no fill"
        m0 = eng.metrics.migration["cancelled"]
        eng._adopt_plan(eng._identity_stack())
        forced_cancel = eng.metrics.migration["cancelled"] > m0
        while eng._executor.active:
            eng._tick_migration()
        recompiled = False
        try:
            eng.assert_no_recompiles()
        except AssertionError:
            recompiled = True
        eng.metrics.flush(eng._plan_stack, eng.ep_ranks, 1)
        s = eng.metrics.summary()
        print(json.dumps({
            "recompiled": recompiled,
            "completed": int(s["completed"]),
            "commits": s["migration_commits"],
            "prebegun": s["migration_prebegun"],
            "hidden_s": s["migration_hidden_s"],
            "forced_cancel": forced_cancel,
            "store_version": np.asarray(eng._store.version).tolist(),
            # consistency: every slot the CURRENT plan can route to holds
            # the right expert (unused replica slots may keep stale ids —
            # dispatch never reads them)
            "store_matches_plan": (lambda se: bool(np.array_equal(
                eng._store.slot_experts[se >= 0], se[se >= 0])))(
                stacked_slot_experts(eng._plan_stack, 4, 1)),
        }))
    """, timeout=1800)
    assert not res["recompiled"]
    assert res["completed"] == 8
    assert res["commits"] >= 1
    assert res["prebegun"] >= 1, "prefetcher never pre-began a migration"
    assert res["hidden_s"] > 0.0
    assert res["forced_cancel"]
    assert res["store_matches_plan"]
