"""Fleet serving tests: HBM budget ledger + global clamp, allocator and
dup-slot quota semantics, tenant admission/SLO classes, arbiter
hysteresis + cost gate, and the meshless/meshed FleetEngine smokes
(zero post-warmup recompiles with arbiter moves applied)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.duplication import duplicate_experts_host
from repro.core.placement import (identity_plan, plan_from_assignments,
                                  quota_limited_plan, stack_plans,
                                  store_bytes_per_rank)
from repro.fleet import (BATCH, INTERACTIVE, ArbiterConfig, FleetAdmission,
                         FleetArbiter, FleetBudget, ModelShare, ModelSignals,
                         SLOClass, kv_block_bytes)
from repro.runtime.diff import vacated_slots
from repro.serve import BlockAllocator
from repro.serve.metrics import RequestTiming, ServeMetrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# budget ledger
# --------------------------------------------------------------------------

def _share(name, *, dup=2, kv=16, weights=1000, entry=10, layers=2,
           experts=8, ranks=4, kvb=8, **kw):
    return ModelShare(name=name, weights_bytes=weights, entry_bytes=entry,
                      num_layers=layers, num_experts=experts, ep_ranks=ranks,
                      dup_slots=dup, kv_blocks=kv, kv_block_bytes=kvb, **kw)


def test_share_bytes_match_placement_math():
    s = _share("m")
    assert s.store_bytes(2) == store_bytes_per_rank(
        8, 4, 2, entry_bytes=10, num_layers=2)
    assert s.provisioned_bytes == 1000 + s.store_bytes(2) + 16 * 8
    assert s.active_bytes == s.provisioned_bytes       # full quotas
    s.kv_block_quota = 4
    s.dup_slot_quota = 1
    assert s.active_bytes == 1000 + s.store_bytes(1) + 4 * 8
    assert s.dup_slot_entry_bytes == 2 * 10


def test_share_quota_defaults_and_clamping():
    assert _share("a").dup_slot_quota == 2
    assert _share("a").kv_block_quota == 16
    s = _share("b", dup_slot_quota=1, kv_block_quota=99)
    assert s.dup_slot_quota == 1
    assert s.kv_block_quota == 16                      # clamped to pool


def test_clamp_unlimited_budget_is_identity():
    b = FleetBudget(0.0)
    b.register(_share("a"))
    b.register(_share("b", dup=1))
    assert b.clamp() == {"a": 2, "b": 1}
    assert b.shares["a"].kv_block_quota == 16


def test_clamp_shrinks_largest_store_first_then_kv():
    b = FleetBudget(0.0)
    big = b.register(_share("big", dup=3))
    small = b.register(_share("small", dup=1))
    full = b.provisioned_bytes()
    # one dup-slot entry is layers*entry = 20 bytes/rank of store; ask to
    # shave a bit more than one slot so exactly the biggest store pays
    b.total_bytes = float(full - 1)
    out = b.clamp()
    assert out == {"big": 2, "small": 1}
    assert b.provisioned_bytes() <= b.total_bytes
    assert big.dup_slot_quota <= big.dup_slots
    # now force past all dup slots into proportional KV-quota shrink
    b2 = FleetBudget(0.0)
    b2.register(_share("a"))
    b2.register(_share("b"))
    no_dup_kv_half = (2 * 1000
                      + 2 * _share("x", dup=0).store_bytes(0)
                      + 16 * 8)                        # half of 2x16 blocks
    b2.total_bytes = float(no_dup_kv_half)
    out2 = b2.clamp()
    assert out2 == {"a": 0, "b": 0}
    assert b2.shares["a"].kv_block_quota < 16
    assert b2.provisioned_bytes() - b2.total_bytes <= sum(
        s.kv_blocks * s.kv_block_bytes for s in b2.shares.values())


def test_clamp_raises_when_residency_alone_overflows():
    b = FleetBudget(10.0)                              # absurdly small
    b.register(_share("a"))
    with pytest.raises(ValueError, match="cannot fit"):
        b.clamp()


def test_transfer_moves_quota_and_respects_bounds():
    b = FleetBudget(0.0)
    b.register(_share("hot", dup_slot_quota=1, kv_block_quota=8))
    b.register(_share("cold", dup_slot_quota=1, kv_block_quota=8))
    assert b.can_transfer("cold", "hot", dup_slots=1, kv_blocks=4)
    b.transfer("cold", "hot", dup_slots=1, kv_blocks=4)
    assert b.shares["hot"].dup_slot_quota == 2
    assert b.shares["cold"].dup_slot_quota == 0
    assert b.shares["hot"].kv_block_quota == 12
    assert b.shares["cold"].kv_block_quota == 4
    # dst at its compiled ceiling: no further dup grant
    assert not b.can_transfer("cold", "hot", dup_slots=1)
    # src exhausted
    assert not b.can_transfer("cold", "hot", kv_blocks=5)
    with pytest.raises(ValueError, match="violates"):
        b.transfer("cold", "hot", dup_slots=1)


def test_transfer_respects_active_byte_budget():
    b = FleetBudget(0.0)
    # hot's slot entries and blocks are pricier than cold's: a 1:1 quota
    # move GROWS the fleet's active bytes, which a tight budget refuses
    b.register(_share("hot", entry=50, dup_slot_quota=1))
    b.register(_share("cold", kvb=1, kv_block_quota=8))
    b.total_bytes = float(b.active_bytes())
    assert not b.can_transfer("cold", "hot", dup_slots=1)  # store grows
    assert not b.can_transfer("cold", "hot", kv_blocks=2)  # 8B>1B blocks
    assert b.can_transfer("hot", "cold", kv_blocks=2)      # shrinks active


def test_budget_summary_has_per_model_rows():
    b = FleetBudget(123.0)
    b.register(_share("m1"))
    s = b.summary()
    for k in ("budget_total_bytes", "m1_weights_bytes", "m1_store_bytes",
              "m1_kv_bytes", "m1_dup_slot_quota", "m1_kv_block_quota"):
        assert k in s


def test_kv_block_bytes_formula():
    # L * bs * kv_heads * head_dim * 2 bytes * (K and V)
    assert kv_block_bytes(2, 8, 4, 16) == 2 * 8 * 4 * 16 * 2 * 2


# --------------------------------------------------------------------------
# allocator quota (deferred handback)
# --------------------------------------------------------------------------

def test_allocator_quota_caps_in_use():
    a = BlockAllocator(num_blocks=9, block_size=4)
    a.set_quota(4)
    got = a.alloc(4)
    assert got is not None and a.in_use == 4
    assert a.alloc(1) is None                          # quota, pool not dry
    assert a.free_blocks == 4
    a.free(got[:1])
    assert a.alloc(1) is not None                      # drained back under


def test_allocator_quota_shrink_below_usage_defers_handback():
    a = BlockAllocator(num_blocks=9, block_size=4)
    got = a.alloc(6)
    a.set_quota(3)                                     # below in_use=6
    assert a.in_use == 6                               # nothing reclaimed
    assert a.alloc(1) is None                          # growth refused
    a.free(got[:3])
    assert a.alloc(1) is None                          # still at quota (3)
    a.free(got[3:4])
    assert a.alloc(1) is not None


def test_allocator_quota_clamps_to_pool():
    a = BlockAllocator(num_blocks=5, block_size=4)
    a.set_quota(99)
    assert a.quota == 4
    a.set_quota(-3)
    assert a.quota == 0
    assert a.alloc(1) is None


# --------------------------------------------------------------------------
# quota-limited placement plans (full compiled geometry)
# --------------------------------------------------------------------------

def _quota_plan(dist, E=8, R=4, D=2, C=4, q=1):
    res = duplicate_experts_host(dist, R, q, C)
    return quota_limited_plan(res.assignments, E, R, D, C, quota=q)


def test_quota_limited_plan_keeps_compiled_geometry():
    dist = [0.5, 0.2, 0.1, 0.05, 0.05, 0.05, 0.03, 0.02]
    full = plan_from_assignments(
        duplicate_experts_host(dist, 4, 2, 4).assignments, 8, 4, 2, 4)
    lim = _quota_plan(dist, q=1)
    for f in ("n_replicas", "replica_table", "pool_expert", "pool_sel"):
        assert np.asarray(getattr(lim, f)).shape \
            == np.asarray(getattr(full, f)).shape, f


def test_quota_limited_plan_respects_per_rank_quota():
    dist = [0.4, 0.3, 0.1, 0.05, 0.05, 0.04, 0.03, 0.03]
    E, R, D, q = 8, 4, 3, 1
    lim = _quota_plan(dist, E=E, R=R, D=D, q=q)
    # extra copies per destination rank = total replicas beyond homes,
    # grouped by the rank owning the replica slot
    e_loc, n_slots = E // R, E // R + D
    table = np.asarray(lim.replica_table)
    n_rep = np.asarray(lim.n_replicas)
    extra = np.zeros(R, np.int64)
    for e in range(E):
        for c in range(1, int(n_rep[e])):
            extra[int(table[e, c]) // n_slots] += 1
    assert (extra <= q).all(), extra


def test_quota_zero_is_identity_at_full_geometry():
    dist = [0.9] + [0.1 / 7] * 7
    lim = _quota_plan(dist, q=0)
    ident = identity_plan(8, 4, 2, 4)
    assert (np.asarray(lim.n_replicas) == 1).all()
    assert np.array_equal(np.asarray(lim.replica_table),
                          np.asarray(ident.replica_table))


def test_quota_shrink_strands_slots_with_zero_transfer():
    dist = [0.5, 0.2, 0.1, 0.05, 0.05, 0.05, 0.03, 0.02]
    E, R, D = 8, 4, 2
    rich = stack_plans([_quota_plan(dist, q=2)] * 2)
    poor = stack_plans([_quota_plan(dist, q=0)] * 2)
    assert vacated_slots(rich, poor, R, D) > 0
    assert vacated_slots(poor, rich, R, D) == 0
    assert vacated_slots(rich, rich, R, D) == 0


# --------------------------------------------------------------------------
# admission + SLO classes
# --------------------------------------------------------------------------

def _timing(tenant, ttft, tpot, toks=5):
    return RequestTiming(rid=0, arrival=0.0, t_first_token=ttft,
                         t_finished=ttft + tpot * (toks - 1),
                         prompt_len=8, new_tokens=toks, tenant=tenant)


def test_admission_routes_and_defaults():
    adm = FleetAdmission(routes={"a": "m1"}, default_model="m0")
    assert adm.route("a") == "m1"
    assert adm.route("unknown") == "m0"
    assert sorted(adm.tenants_for("m1")) == ["a"]
    strict = FleetAdmission(routes={"a": "m1"})
    with pytest.raises(KeyError):
        strict.route("unknown")


def test_strictest_slo_takes_min_per_bound():
    adm = FleetAdmission(
        routes={"chat": "m", "batch": "m"},
        slos={"chat": INTERACTIVE, "batch": BATCH})
    s = adm.strictest_slo("m")
    assert s.slo_ttft == INTERACTIVE.slo_ttft
    assert s.slo_tpot == INTERACTIVE.slo_tpot
    assert adm.strictest_slo("other") == adm.default_slo


def test_tenant_and_model_attainment_judged_per_class():
    adm = FleetAdmission(
        routes={"chat": "m", "batch": "m"},
        slos={"chat": SLOClass("chat", slo_ttft=1.0, slo_tpot=0.5),
              "batch": BATCH})
    m = ServeMetrics()
    m.timings.extend([
        _timing("chat", ttft=0.5, tpot=0.1),           # meets chat SLO
        _timing("chat", ttft=5.0, tpot=0.1),           # TTFT miss
        _timing("batch", ttft=5.0, tpot=0.1),          # batch has no TTFT
    ])
    assert adm.tenant_attainment(m, "chat") == 0.5
    assert adm.tenant_attainment(m, "batch") == 1.0
    assert adm.model_attainment(m, "m") == 0.5         # worst tenant
    assert adm.model_attainment(m, "empty-model") == 1.0


def test_slo_attainment_defaults_to_one_without_completions():
    assert ServeMetrics().slo_attainment(tenant="x") == 1.0


# --------------------------------------------------------------------------
# arbiter: pressure, hysteresis, cost gate
# --------------------------------------------------------------------------

def _signals(hot_attain=0.5, hot_queue=8, cold_attain=1.0, step_s=0.1,
             entry=64, hot_skew=2.0):
    return {
        "hot": ModelSignals(slo_attainment=hot_attain, queue_depth=hot_queue,
                            window_skew=hot_skew, step_s=step_s,
                            dup_entry_bytes=entry),
        "cold": ModelSignals(slo_attainment=cold_attain, queue_depth=0,
                             window_skew=1.0, step_s=step_s,
                             dup_entry_bytes=entry),
    }


def _arbiter(patience=2, **kw):
    b = FleetBudget(0.0)
    b.register(_share("hot", dup_slot_quota=1, kv_block_quota=8))
    b.register(_share("cold", dup_slot_quota=1, kv_block_quota=8))
    return FleetArbiter(ArbiterConfig(patience=patience, window_iters=4,
                                      kv_blocks_per_move=4,
                                      kv_floor_blocks=2, **kw), b)


def test_arbiter_waits_out_patience_then_moves():
    arb = _arbiter(patience=2)
    assert arb.observe(1.0, _signals()) == []          # vote 1 of 2
    moves = arb.observe(2.0, _signals())
    assert len(moves) == 1
    mv = moves[0]
    assert (mv.src, mv.dst) == ("cold", "hot")
    assert mv.kv_blocks == 4
    assert arb.budget.shares["hot"].kv_block_quota == 12
    assert arb.budget.shares["cold"].kv_block_quota == 4
    assert "cold->hot" in mv.explain()


def test_arbiter_resets_votes_when_pressure_gap_closes():
    arb = _arbiter(patience=2)
    arb.observe(1.0, _signals())
    arb.observe(2.0, _signals(hot_attain=1.0, hot_queue=0,
                              hot_skew=1.0))               # gap closes
    assert arb.observe(3.0, _signals()) == []          # vote restarted
    assert len(arb.observe(4.0, _signals())) == 1


def test_arbiter_single_model_never_moves():
    arb = _arbiter(patience=1)
    assert arb.observe(1.0, {"hot": _signals()["hot"]}) == []


def test_arbiter_cost_gate_blocks_dup_but_not_kv():
    # an absurd per-slot migration cost vs a tiny window gain: the dup
    # grant must be rejected by should_migrate, the KV move still lands
    arb = _arbiter(patience=1)
    sig = _signals(step_s=1e-9, entry=10 ** 15)
    moves = arb.observe(1.0, sig)
    assert len(moves) == 1
    assert moves[0].dup_slots == 0
    assert moves[0].kv_blocks == 4
    # cheap migration + real gain: dup slot moves too
    arb2 = _arbiter(patience=1)
    moves2 = arb2.observe(1.0, _signals(step_s=0.5, entry=64))
    assert moves2[0].dup_slots == 1
    assert moves2[0].stall_s >= 0.0
    assert arb2.budget.shares["hot"].dup_slot_quota == 2


def test_arbiter_kv_floor_protects_donor():
    arb = _arbiter(patience=1)
    for t in range(1, 6):
        arb.observe(float(t), _signals(step_s=1e-9, entry=10 ** 15))
    # cold started at 8; floor 2 with 4-block moves leaves exactly 4
    assert arb.budget.shares["cold"].kv_block_quota == 4
    assert arb.budget.shares["hot"].kv_block_quota == 12


def test_arbiter_max_moves_cap():
    arb = _arbiter(patience=1, max_moves=1)
    arb.observe(1.0, _signals())
    assert arb.observe(2.0, _signals()) == []
    assert len(arb.moves) == 1


# --------------------------------------------------------------------------
# ServeMetrics model label: two resident instances share one registry
# --------------------------------------------------------------------------

def test_serve_metrics_model_label_keeps_instances_separate():
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    m1 = ServeMetrics(registry=reg, model="m1")
    m2 = ServeMetrics(registry=reg, model="m2")
    m1.timings.append(_timing("", ttft=0.5, tpot=0.1))
    m1.record_completion(m1.timings[-1])
    m2.timings.append(_timing("", ttft=0.7, tpot=0.1))
    m2.record_completion(m2.timings[-1])
    snap = reg.snapshot()
    assert snap['serve_requests_completed_total{model="m1"}'] == 1.0
    assert snap['serve_requests_completed_total{model="m2"}'] == 1.0
    prom = reg.to_prometheus()
    assert 'model="m1"' in prom and 'model="m2"' in prom


def test_serve_metrics_without_model_keeps_unlabeled_series():
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    m = ServeMetrics(registry=reg)
    m.timings.append(_timing("", ttft=0.5, tpot=0.1))
    m.record_completion(m.timings[-1])
    assert "serve_requests_completed_total" in reg.snapshot()


# --------------------------------------------------------------------------
# FleetEngine end-to-end (meshless smoke; the meshed smoke is slow-marked)
# --------------------------------------------------------------------------

def _fleet(enable_arbiter=True, hbm=0.0, trace=False):
    import jax

    from repro.configs.registry import get_config
    from repro.fleet import FleetEngine, FleetModelSpec
    from repro.models.transformer import init_model
    from repro.serve import ContinuousConfig

    cfg = get_config("mixtral-8x7b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = ContinuousConfig(max_slots=2, prefill_len=16, block_size=8,
                            max_len=32, strategy="dist_only",
                            predict_interval=2, dup_slots=1,
                            metrics_window=2)
    adm = FleetAdmission(
        routes={"a": "m1", "b": "m2"},
        slos={"a": SLOClass("a", slo_ttft=4.0), "b": BATCH})
    specs = [FleetModelSpec("m1", cfg, params, ccfg),
             FleetModelSpec("m2", cfg, params, ccfg)]
    fleet = FleetEngine(specs, admission=adm, hbm_budget_bytes=hbm,
                        arbiter_cfg=ArbiterConfig(window_iters=2,
                                                  patience=1),
                        enable_arbiter=enable_arbiter, trace=trace)
    return fleet, cfg


def _fleet_requests(cfg, n=6):
    from repro.serve import ServeRequest
    rng = np.random.default_rng(0)
    return [ServeRequest(rid=i, arrival=0.25 * i,
                         tokens=rng.integers(0, cfg.vocab_size, 8),
                         max_new_tokens=3,
                         tenant="a" if i % 2 == 0 else "b")
            for i in range(n)]


def test_fleet_engine_meshless_smoke():
    fleet, cfg = _fleet(trace=True)
    fleet.warmup()
    for r in _fleet_requests(cfg):
        fleet.submit(r)
    assert len(fleet.engines["m1"].scheduler.waiting) == 3
    assert len(fleet.engines["m2"].scheduler.waiting) == 3
    now, n = 0.0, 0
    while fleet.has_work() and n < 60:
        fleet.step(now)
        now += 0.25
        n += 1
    assert not fleet.has_work()
    fleet.assert_no_recompiles()
    s = fleet.summary()
    assert s["fleet_completed"] == 6.0
    assert s["fleet_models"] == 2.0
    assert 0.0 <= s["fleet_slo_attainment"] <= 1.0
    assert s["m1_kv_block_quota"] > 0
    # merged trace: one process row per model, schema-valid
    from repro.obs import validate_chrome_trace
    doc = fleet.merged_trace()
    assert validate_chrome_trace(doc) == []
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert pids == {1, 2}


def test_fleet_engine_applies_manual_quota_move():
    fleet, cfg = _fleet(enable_arbiter=False)
    fleet.warmup()
    eng1, eng2 = fleet.engines["m1"], fleet.engines["m2"]
    full = eng1.allocator.quota
    fleet.budget.transfer("m2", "m1", kv_blocks=0, dup_slots=0)
    # apply a KV quota move by hand the way _arbitrate does
    fleet.budget.shares["m2"].kv_block_quota -= 2
    fleet.budget.shares["m1"].kv_block_quota = min(
        fleet.budget.shares["m1"].kv_blocks,
        fleet.budget.shares["m1"].kv_block_quota)      # ceiling respected
    eng2.allocator.set_quota(fleet.budget.shares["m2"].kv_block_quota)
    assert eng2.allocator.quota == full - 2
    for r in _fleet_requests(cfg):
        fleet.submit(r)
    now, n = 0.0, 0
    while fleet.has_work() and n < 60:
        fleet.step(now)
        now += 0.25
        n += 1
    assert not fleet.has_work()
    fleet.assert_no_recompiles()


def test_fleet_engine_initial_quota_below_ceiling():
    import jax

    from repro.configs.registry import get_config
    from repro.fleet import FleetEngine, FleetModelSpec
    from repro.models.transformer import init_model
    from repro.serve import ContinuousConfig

    cfg = get_config("mixtral-8x7b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = ContinuousConfig(max_slots=2, prefill_len=16, block_size=8,
                            max_len=32, strategy="none", dup_slots=1)
    fleet = FleetEngine(
        [FleetModelSpec("m", cfg, params, ccfg,
                        dup_slot_quota=0, kv_block_quota=3)])
    eng = fleet.engines["m"]
    assert eng.allocator.quota == 3
    assert eng.dup_slot_quota == 0
    assert fleet.budget.shares["m"].kv_block_quota == 3


def test_fleet_rejects_duplicate_model_names():
    import jax

    from repro.configs.registry import get_config
    from repro.fleet import FleetEngine, FleetModelSpec
    from repro.models.transformer import init_model
    from repro.serve import ContinuousConfig

    cfg = get_config("mixtral-8x7b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = ContinuousConfig(max_slots=2, prefill_len=16, block_size=8,
                            max_len=32, strategy="none")
    with pytest.raises(ValueError, match="duplicate"):
        FleetEngine([FleetModelSpec("m", cfg, params, ccfg),
                     FleetModelSpec("m", cfg, params, ccfg)])


@pytest.mark.slow
def test_fleet_meshed_smoke_arbiter_move_no_recompile():
    """Two model instances on a real 2x4 EP mesh: starve one model's KV
    quota, drive load at it, and require >= 1 arbiter move and zero
    post-warmup recompiles across the whole fleet."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, numpy as np
        from repro.configs.registry import get_config
        from repro.fleet import (ArbiterConfig, BATCH, FleetAdmission,
                                 FleetEngine, FleetModelSpec, SLOClass)
        from repro.models.transformer import init_model
        from repro.serve import ContinuousConfig, ServeRequest

        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("mixtral-8x7b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        ccfg = ContinuousConfig(max_slots=2, prefill_len=16, block_size=8,
                                max_len=32, strategy="dist_only",
                                predict_interval=4, dup_slots=2,
                                metrics_window=4)
        adm = FleetAdmission(
            routes={"a": "m1", "b": "m2"},
            slos={"a": SLOClass("a", slo_ttft=0.75, slo_tpot=1.0),
                  "b": BATCH})
        specs = [FleetModelSpec(n, cfg, params, ccfg,
                                dup_slot_quota=1, kv_block_quota=4)
                 for n in ("m1", "m2")]
        fleet = FleetEngine(
            specs, mesh=mesh, ep_ranks=4, admission=adm,
            arbiter_cfg=ArbiterConfig(window_iters=4, patience=1,
                                      queue_norm=2.0, kv_blocks_per_move=2,
                                      kv_floor_blocks=1),
            enable_arbiter=True)
        fleet.warmup()
        rng = np.random.default_rng(0)
        for i in range(8):
            fleet.submit(ServeRequest(
                rid=i, arrival=0.25 * i,
                tokens=rng.integers(0, cfg.vocab_size, 12),
                max_new_tokens=4, tenant="a"))
        now, n = 0.0, 0
        while fleet.has_work() and n < 80:
            fleet.step(now)
            now += 0.25
            n += 1
        recompiled = 0
        try:
            fleet.assert_no_recompiles()
        except AssertionError:
            recompiled = 1
        s = fleet.summary()
        print(json.dumps({
            "drained": not fleet.has_work(),
            "recompiled": recompiled,
            "moves": s["fleet_arbiter_moves"],
            "m1_kv": s["m1_kv_block_quota"],
            "completed": s["fleet_completed"],
        }))
    """)
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=900, env=dict(os.environ, PYTHONPATH=os.path.join(ROOT,
                                                                  "src")))
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["drained"], res
    assert res["recompiled"] == 0, res
    assert res["moves"] >= 1, res
    assert res["m1_kv"] > 4, res                       # quota moved to m1
