"""Replica-weight migration runtime (repro.runtime).

Four layers of coverage:

* plan_diff properties — diff(p, p) is empty; applying a diff to the old
  slot map reproduces the target on every live slot; diffs touch replica
  slots only (home assignments are fixed by construction);
* store construction — every live slot's buffer equals the occupying
  expert's weights; chunked migration (mesh-less step) reproduces the
  store built directly from the target plan;
* EP forward equivalence — a multi-device forward reading the store is
  BIT-EXACT against the per-step gather-pool oracle across dup_slots,
  top_k and predicted mode, and its jaxpr contains no weight all_gather
  (the identity-plan gather skip is exercised the same way);
* engine integration — a meshed ContinuousEngine in store mode serves,
  migrates on re-plans under a chunk budget, commits, and never
  recompiles after warmup.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core.duplication import duplicate_experts_host
from repro.core.placement import (identity_plan, plan_dims, slot_expert_map,
                                  stack_plans)
from repro.data.synthetic import skewed_distribution
from repro.runtime import (MigrationExecutor, ReplicaStore, apply_diff,
                           entry_bytes, make_migrate_step, migrate_all,
                           migration_stall_s, plan_diff, should_migrate,
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


# ---------------------------------------------------------------------------
# plan_diff properties
# ---------------------------------------------------------------------------

@given(st.integers(1, 3), st.integers(1, 2), st.floats(1.5, 7.0))
@settings(max_examples=25, deadline=None)
def test_plan_diff_self_is_empty(layers, dup, skew):
    p = stack_plans([duplicate_experts_host(
        skewed_distribution(E, skew), R, dup, 4).plan
        for _ in range(layers)])
    assert plan_diff(p, p, R, dup).num_entries == 0


@given(st.integers(1, 3), st.integers(1, 2), st.integers(0, 50))
@settings(max_examples=25, deadline=None)
def test_plan_diff_apply_reproduces_target(layers, dup, seed):
    old = (_identity_stack(layers, dup) if seed % 2
           else _dup_stack(layers, dup, seed))
    new = _dup_stack(layers, dup, seed + 1, base_skew=3.0)
    diff = plan_diff(old, new, R, dup)
    se_old = stacked_slot_experts(old, R, dup)
    se_new = stacked_slot_experts(new, R, dup)
    applied = apply_diff(se_old, diff)
    live = se_new >= 0
    assert np.array_equal(applied[live], se_new[live])
    # only replica slots may move, and only to a LIVE assignment
    e_loc, n_slots = plan_dims(E, R, dup)
    assert np.all(diff.dst_slot % n_slots >= e_loc)
    assert np.all(diff.src_expert >= 0)


def test_slot_expert_map_identity_and_home():
    dup = 2
    e_loc, n_slots = plan_dims(E, R, dup)
    se = slot_expert_map(identity_plan(E, R, dup, 4), R, dup)
    for e in range(E):
        assert se[(e // e_loc) * n_slots + e % e_loc] == e
    # identity plan: every replica slot is unused
    assert np.all(se.reshape(R, n_slots)[:, e_loc:] == -1)


# ---------------------------------------------------------------------------
# store construction + mesh-less migration
# ---------------------------------------------------------------------------

def _toy_experts(layers, d=4, f=6, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w_gate": jnp.asarray(rng.normal(size=(layers, E, d, f)), jnp.float32),
        "w_up": jnp.asarray(rng.normal(size=(layers, E, d, f)), jnp.float32),
        "w_down": jnp.asarray(rng.normal(size=(layers, E, f, d)), jnp.float32),
    }


def test_store_live_slots_hold_expert_weights():
    layers, dup = 2, 2
    experts = _toy_experts(layers)
    plan = _dup_stack(layers, dup)
    store = ReplicaStore.from_params(experts, plan, num_experts=E,
                                     ep_ranks=R, dup_slots=dup)
    se = stacked_slot_experts(plan, R, dup)
    for k, w in store.weights.items():
        ref = np.asarray(experts[k])
        got = np.asarray(w)
        for l in range(layers):
            for s in np.nonzero(se[l] >= 0)[0]:
                assert np.array_equal(got[l, s], ref[l, se[l, s]]), (k, l, s)
    assert store.entry_bytes == entry_bytes(experts)


@pytest.mark.parametrize("chunk", [1, 3, 64])
def test_meshless_migration_reaches_target_store(chunk):
    layers, dup = 3, 2
    experts = _toy_experts(layers)
    old, new = _identity_stack(layers, dup), _dup_stack(layers, dup, seed=2)
    store = ReplicaStore.from_params(experts, old, num_experts=E,
                                     ep_ranks=R, dup_slots=dup)
    step = make_migrate_step(None, num_experts=E, ep_ranks=R, dup_slots=dup)
    diff = plan_diff(old, new, R, dup)
    assert diff.num_entries > 0
    got = migrate_all(step, store.weights, experts, diff, chunk=chunk)
    ref = ReplicaStore.from_params(experts, new, num_experts=E,
                                   ep_ranks=R, dup_slots=dup)
    live = stacked_slot_experts(new, R, dup) >= 0
    for k in got:
        assert np.array_equal(np.asarray(got[k])[live],
                              np.asarray(ref.weights[k])[live]), k


def test_executor_budget_and_commit_bookkeeping():
    layers, dup = 2, 2
    experts = _toy_experts(layers)
    old, new = _identity_stack(layers, dup), _dup_stack(layers, dup, seed=3)
    store = ReplicaStore.from_params(experts, old, num_experts=E,
                                     ep_ranks=R, dup_slots=dup)
    step = make_migrate_step(None, num_experts=E, ep_ranks=R, dup_slots=dup)
    diff = plan_diff(old, new, R, dup)
    se_new = stacked_slot_experts(new, R, dup)
    ex = MigrationExecutor(step, experts, store.entry_bytes, chunk=2,
                           chunks_per_tick=1)
    ex.begin(store.weights, diff, new)
    ticks, moved_total, commit = 0, 0, None
    while commit is None:
        commit, moved = ex.tick()
        moved_total += moved
        ticks += 1
        assert ticks <= diff.num_entries + 1, "executor failed to converge"
    assert not ex.active
    assert moved_total == diff.num_entries * store.entry_bytes
    assert ticks == -(-diff.num_entries // 2)      # one 2-entry chunk per tick
    weights, plan, se = commit
    v0 = store.version.copy()
    store.adopt(weights, se)
    assert np.array_equal(se, se_new)
    changed = np.any(stacked_slot_experts(old, R, dup) != se_new, axis=1)
    assert np.array_equal(store.version - v0, changed.astype(np.int64))


def test_executor_cancel_discards_in_flight_migration():
    """A superseded target (e.g. the controller switching to strategy
    "none" mid-fill) must not commit later: cancel() drops the back
    buffer and the next tick is a no-op."""
    layers, dup = 2, 2
    experts = _toy_experts(layers)
    old, new = _identity_stack(layers, dup), _dup_stack(layers, dup, seed=4)
    store = ReplicaStore.from_params(experts, old, num_experts=E,
                                     ep_ranks=R, dup_slots=dup)
    step = make_migrate_step(None, num_experts=E, ep_ranks=R, dup_slots=dup)
    diff = plan_diff(old, new, R, dup)
    ex = MigrationExecutor(step, experts, store.entry_bytes, chunk=1,
                           chunks_per_tick=1)
    ex.begin(store.weights, diff, new)
    ex.tick()                          # partial fill in the back buffer
    assert ex.active
    ex.cancel()
    assert not ex.active
    assert ex.tick() == (None, 0)      # nothing left to commit
    # live buffers were never touched by the abandoned fill
    ref = ReplicaStore.from_params(experts, old, num_experts=E,
                                   ep_ranks=R, dup_slots=dup)
    for k in store.weights:
        assert np.array_equal(np.asarray(store.weights[k]),
                              np.asarray(ref.weights[k])), k


def test_cost_model_gate():
    assert should_migrate(stall_s=0.0, gain_s=0.0)

    class HW:
        link_bw = 1e9
    assert migration_stall_s(2e9, HW) == pytest.approx(2.0)
    assert not should_migrate(stall_s=2.0, gain_s=0.5)


def test_gps_charges_migration_to_duplicating_strategies():
    from repro.configs.registry import get_config
    from repro.core.gps import run_gps
    from repro.core.simulator import A100_PCIE
    cfg = get_config("mixtral-8x7b")
    base = run_gps(cfg, A100_PCIE, skew=1.8)
    heavy = run_gps(cfg, A100_PCIE, skew=1.8,
                    migration_stall_s=base.baseline.total * 10)
    # the baseline never migrates; duplicating strategies carry the stall
    assert heavy.baseline.total == base.baseline.total
    assert heavy.dist_only.total > base.dist_only.total
    assert all(h.total > b.total for h, b in
               zip(heavy.t2e_points, base.t2e_points))
    # heavy churn flips the online verdict to plain EP
    from repro.core.gps import recommend_strategy
    name, _ = recommend_strategy(cfg, A100_PCIE, skew=1.8,
                                 migration_stall_s=base.baseline.total * 10)
    assert name == "none"


# ---------------------------------------------------------------------------
# multi-device equivalence + no-collective guarantee
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_store_forward_matches_gather_multidevice():
    """Store-fed EP forward is BIT-EXACT vs the per-step gather pool
    across dup_slots/top_k/predicted, including after a chunked migration
    to a new plan; the store jaxpr has no weight all_gather."""
    res = run_sub("""
        import dataclasses, itertools
        from repro.configs.registry import get_config
        from repro.core.duplication import duplicate_experts_host
        from repro.core.placement import stack_plans
        from repro.data.synthetic import skewed_distribution
        from repro.models.transformer import Runtime, forward, init_model
        from repro.runtime import (ReplicaStore, make_migrate_step,
                                   migrate_all, plan_diff,
                                   stacked_slot_experts)

        base = get_config("mixtral-8x7b").reduced()
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4, use_duplication=True)
        E = base.moe.num_experts
        out = {}
        for top_k, dup, predicted in itertools.product((1, 2), (1, 2),
                                                       (False, True)):
            cfg = dataclasses.replace(base, moe=dataclasses.replace(
                base.moe, top_k=top_k, duplication_slots=dup))
            params = init_model(jax.random.PRNGKey(0), cfg)
            experts = params["layers"]["moe"]["experts"]
            B, S = 4, 32
            batch = {"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)}
            pred = (jnp.zeros((cfg.num_layers, B, S, top_k), jnp.int32)
                    if predicted else None)
            plan = stack_plans([duplicate_experts_host(
                skewed_distribution(E, 2.5 + l), 4, dup, 4).plan
                for l in range(cfg.num_layers)])
            store = ReplicaStore.from_params(
                experts, plan, num_experts=E, ep_ranks=4, dup_slots=dup,
                mesh=mesh)
            # migrate to a DIFFERENT plan so equivalence also covers
            # store contents written by the chunked migration step
            plan2 = stack_plans([duplicate_experts_host(
                skewed_distribution(E, 5.0 - l), 4, dup, 4).plan
                for l in range(cfg.num_layers)])
            diff = plan_diff(plan, plan2, 4, dup)
            if diff.num_entries:
                mig = make_migrate_step(mesh, num_experts=E, ep_ranks=4,
                                        dup_slots=dup)
                w2 = migrate_all(mig, store.weights, experts, diff, chunk=3)
                store.adopt(w2, diff.target_slot_experts)
            lg, _, sg = jax.jit(lambda p, b, pl, pr: forward(
                p, cfg, b, rt, mode="train", plan=pl, predicted_idx=pr)
            )(params, batch, plan2, pred)
            ls, _, ss = jax.jit(lambda p, b, pl, pr, sw: forward(
                p, cfg, b, rt, mode="train", plan=pl, predicted_idx=pr,
                slot_weights=sw)
            )(params, batch, plan2, pred, store.weights)
            key = f"k{top_k}_d{dup}_p{int(predicted)}"
            out[key] = {
                "diff": float(jnp.abs(lg.astype(jnp.float32)
                                      - ls.astype(jnp.float32)).max()),
                "counts_eq": bool(jnp.array_equal(sg["expert_counts"],
                                                  ss["expert_counts"])),
                "slots_eq": bool(jnp.array_equal(sg["slot_counts"],
                                                 ss["slot_counts"])),
                "migrated": int(diff.num_entries),
            }
        # no weight collective in the store-fed program (tokens still
        # all_to_all); the gather program must still contain the pool
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, duplication_slots=1))
        params = init_model(jax.random.PRNGKey(0), cfg)
        plan = stack_plans([duplicate_experts_host(
            skewed_distribution(E, 2.5), 4, 1, 4).plan
            for _ in range(cfg.num_layers)])
        store = ReplicaStore.from_params(
            params["layers"]["moe"]["experts"], plan, num_experts=E,
            ep_ranks=4, dup_slots=1, mesh=mesh)
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32)}
        jx_store = str(jax.make_jaxpr(lambda p, b, pl, sw: forward(
            p, cfg, b, rt, mode="train", plan=pl, slot_weights=sw))(
            params, batch, plan, store.weights))
        jx_gather = str(jax.make_jaxpr(lambda p, b, pl: forward(
            p, cfg, b, rt, mode="train", plan=pl))(params, batch, plan))
        out["store_has_allgather"] = "all_gather" in jx_store
        out["gather_has_allgather"] = "all_gather" in jx_gather
        print(json.dumps(out))
    """, timeout=1800)
    assert not res.pop("store_has_allgather")
    assert res.pop("gather_has_allgather")
    migrated_any = False
    for key, r in res.items():
        assert r["diff"] == 0.0, (key, r)
        assert r["counts_eq"] and r["slots_eq"], (key, r)
        migrated_any |= r["migrated"] > 0
    assert migrated_any, "no case exercised the migration step"


@pytest.mark.slow
def test_identity_plan_skips_pool_gather_but_matches():
    """The lax.cond gather skip: identity plan (dup slots compiled in but
    nothing duplicated) produces the same logits as a forced gather, and
    the decode (replicated-token) path agrees too."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.core.placement import identity_plan, stack_plans
        from repro.models.transformer import Runtime, forward, init_model, \\
            init_cache
        from repro.train.steps import make_decode_step

        base = get_config("mixtral-8x7b").reduced()
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, duplication_slots=1, capacity_factor=8.0))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4, use_duplication=True)
        params = init_model(jax.random.PRNGKey(0), cfg)
        E = cfg.moe.num_experts
        idp = stack_plans([identity_plan(E, 4, 1, 4)
                           for _ in range(cfg.num_layers)])
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (4, 32), 0, cfg.vocab_size)}
        # identity plan exercises the cond's skip branch; the dense
        # reference (no EP at all) is the ground truth
        lg, _, _ = jax.jit(lambda p, b, pl: forward(
            p, cfg, b, rt, mode="train", plan=pl))(params, batch, idp)
        ref, _, _ = forward(params, cfg, batch, Runtime(), mode="train")
        tok = jnp.ones((4, 1), jnp.int32)
        cache = init_cache(cfg, rt, 4, 32)
        with mesh:
            _, dl, _, _ = jax.jit(lambda p, t, c, pl: make_decode_step(
                cfg, rt)(p, t, c, 5, pl))(params, tok, cache, idp)
        _, dr, _, _ = make_decode_step(cfg, Runtime())(
            params, tok, init_cache(cfg, Runtime(), 4, 32), 5)
        print(json.dumps({
            "train_diff": float(jnp.abs(lg.astype(jnp.float32)
                                        - ref.astype(jnp.float32)).max()),
            "decode_diff": float(jnp.abs(dl.astype(jnp.float32)
                                         - dr.astype(jnp.float32)).max()),
        }))
    """)
    assert res["train_diff"] < 0.1          # bf16 path differences only
    assert res["decode_diff"] < 0.1


@pytest.mark.slow
def test_continuous_engine_store_migrates_without_recompiles():
    """Meshed ContinuousEngine in store mode: serves a workload, re-plans
    under a 1-chunk-per-step budget, commits migrations, and performs
    ZERO XLA compilations after warmup."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import init_model
        from repro.serve import ContinuousConfig, ContinuousEngine
        from repro.serve.scheduler import ServeRequest

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("mixtral-8x7b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        ccfg = ContinuousConfig(max_slots=4, prefill_len=32, block_size=16,
                                max_len=48, strategy="dist_only",
                                predict_interval=2, dup_slots=1,
                                metrics_window=4, migrate_chunks_per_step=1)
        eng = ContinuousEngine(cfg, params, ccfg, mesh=mesh, ep_ranks=4)
        eng.warmup()
        rng = np.random.default_rng(0)
        for i in range(6):
            eng.submit(ServeRequest(
                rid=i, arrival=0.0,
                tokens=rng.integers(0, cfg.vocab_size, 16).tolist(),
                max_new_tokens=4))
        n = 0
        while eng.has_work() and n < 40:
            eng.step(float(n)); n += 1
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
            "replans": s["migration_replans"],
            "commits": s["migration_commits"],
            "moved": s["migration_bytes_moved"],
            "store_version": np.asarray(eng._store.version).tolist(),
        }))
    """, timeout=1800)
    assert not res["recompiled"]
    assert res["completed"] == 6
    assert res["replans"] >= 1
    assert res["commits"] >= 1
    assert res["moved"] > 0
    assert sum(res["store_version"]) >= 1    # per-layer versions advanced
