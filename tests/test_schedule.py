"""Token rescheduling subsystem tests (repro.schedule).

Four layers of coverage:

* quota representation — even quotas reproduce the legacy round-robin
  split; share -> quota -> share round-trips within quantisation error;
  quota rows are monotone with dead columns pinned unreachable;
* scheduler properties (greedy AND lp) — scheduled splits never exceed
  the even split's slot overflow, conserve every token (rows are
  distributions over live copies), never worsen rank imbalance, and are
  deterministic for a fixed input;
* dispatch equivalence — the sort packer consuming a reschedule quota
  stack matches the one-hot oracle bit for bit on a multi-device EP
  mesh (the same guarantee test_dispatch_equivalence.py gives the
  even-split path);
* engine integration — a meshed ContinuousEngine with the reschedule
  lever on serves a skewed trace with ZERO dropped tokens at smoke
  shapes and ZERO post-warmup recompiles.
"""

import numpy as np
import pytest

from repro.core.duplication import duplicate_experts_host
from repro.data.synthetic import skewed_distribution
from repro.schedule import (RESCHED_Q, even_quota, even_quota_stack,
                            even_shares, make_scheduler,
                            quota_realized_shares)
from tests.test_distributed import run_sub

EP_RANKS, DUP_SLOTS, MAX_COPIES = 4, 2, 4


def _plan(dist, seed=0):
    return duplicate_experts_host(np.asarray(dist, np.float64), EP_RANKS,
                                  DUP_SLOTS, MAX_COPIES).plan


def _skewed_case(E=16, alpha=3.0, tokens=4096, seed=0):
    rng = np.random.default_rng(seed)
    dist = skewed_distribution(E, alpha, rng=rng)
    counts = np.asarray(dist, np.float64) * tokens
    return counts, _plan(dist)


# --------------------------------------------------------------------------
# quota representation
# --------------------------------------------------------------------------

def test_even_quota_reproduces_round_robin_shares():
    counts, plan = _skewed_case(seed=1)
    n_rep = np.asarray(plan.n_replicas, np.int64)
    got = quota_realized_shares(even_quota(plan))
    want = even_shares(n_rep, np.asarray(plan.replica_table).shape[1])
    np.testing.assert_allclose(got, want, atol=2.0 / RESCHED_Q)


def test_quota_roundtrip_and_monotonicity():
    counts, plan = _skewed_case(seed=2)
    sched = make_scheduler("greedy")
    res = sched.plan_layer(counts, plan, ep_ranks=EP_RANKS,
                           dup_slots=DUP_SLOTS, cap=counts.sum() / 8)
    q = res.quota
    n_rep = np.asarray(plan.n_replicas, np.int64)
    assert q.dtype == np.int32 and q.shape == res.shares.shape
    # monotone rows; dead columns unreachable; realized ~= planned shares
    assert (np.diff(q, axis=1) >= 0).all()
    cols = np.arange(q.shape[1])[None, :]
    assert (q[cols >= np.maximum(n_rep, 1)[:, None] - 1] == RESCHED_Q).all()
    np.testing.assert_allclose(quota_realized_shares(q), res.shares,
                               atol=2.0 / RESCHED_Q)


def test_even_quota_stack_shape_is_static():
    _, plan = _skewed_case(seed=3)
    stack = even_quota_stack(6, plan)
    E, C = np.asarray(plan.replica_table).shape
    assert stack.shape == (6, E, C) and stack.dtype == np.int32
    assert (stack[0] == stack[-1]).all()


# --------------------------------------------------------------------------
# scheduler properties (both impls behind one interface)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["greedy", "lp"])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_scheduler_never_worse_than_even_split(impl, seed):
    counts, plan = _skewed_case(alpha=2.0 + seed, seed=seed)
    n_rep = np.asarray(plan.n_replicas, np.int64)
    cap = counts.sum() / (counts.shape[0] * 0.6)    # tight: forces overflow
    res = make_scheduler(impl).plan_layer(counts, plan, ep_ranks=EP_RANKS,
                                          dup_slots=DUP_SLOTS, cap=cap)
    sh = res.shares
    cols = np.arange(sh.shape[1])[None, :]
    live = cols < np.maximum(n_rep, 1)[:, None]
    # conservation: every row a distribution over live copies only
    assert (sh >= 0).all() and (sh[~live] == 0).all()
    np.testing.assert_allclose(sh.sum(1), 1.0, atol=1e-9)
    tok = (sh * counts[:, None]).sum()
    np.testing.assert_allclose(tok, counts.sum(), rtol=1e-12)
    # capacity: scheduled split never overflows more than the even split
    assert res.overflow_sched <= res.overflow_even + 1e-9, impl
    # balance: rank imbalance never degrades
    assert res.imbalance_sched <= res.imbalance_even + 1e-9, impl
    assert 0.0 <= res.overflow_absorbed_frac <= 1.0


@pytest.mark.parametrize("impl", ["greedy", "lp"])
@pytest.mark.parametrize("seed", [0, 1, 4])
def test_scheduler_strictly_levels_rank_loads(impl, seed):
    """With replicas on other ranks and headroom under the slot cap, the
    scheduler must strictly reduce rank imbalance by moving real token
    mass — without manufacturing any slot overflow (the quota only splits
    an expert's traffic across its OWN copies, so per-expert overflow can
    never beat the even split; absorption of genuine overflow is the
    dispatch rescue round's job, tested below at the engine level)."""
    counts, plan = _skewed_case(E=16, alpha=5.0, seed=seed)
    cap = counts.mean() * 4
    res = make_scheduler(impl).plan_layer(counts, plan, ep_ranks=EP_RANKS,
                                          dup_slots=DUP_SLOTS, cap=cap)
    assert res.overflow_even == 0.0 and res.overflow_sched == 0.0
    assert res.imbalance_sched < res.imbalance_even - 0.01, impl
    assert res.moved_tokens > 0


@pytest.mark.parametrize("impl", ["greedy", "lp"])
def test_scheduler_deterministic(impl):
    counts, plan = _skewed_case(seed=11)
    kw = dict(ep_ranks=EP_RANKS, dup_slots=DUP_SLOTS,
              cap=counts.sum() / 10)
    a = make_scheduler(impl).plan_layer(counts, plan, **kw)
    b = make_scheduler(impl).plan_layer(counts, plan, **kw)
    assert np.array_equal(a.quota, b.quota)
    assert np.array_equal(a.shares, b.shares)


def test_plan_stack_stacks_per_layer_quotas():
    L, E = 3, 16
    rng = np.random.default_rng(5)
    counts = np.stack([skewed_distribution(E, 2.0 + l) * 2048
                       for l in range(L)])
    plans = [_plan(counts[l] / counts[l].sum()) for l in range(L)]
    quota, results = make_scheduler("greedy").plan_stack(
        counts, plans, ep_ranks=EP_RANKS, dup_slots=DUP_SLOTS, cap=256.0)
    assert quota.shape[0] == L and quota.dtype == np.int32
    assert len(results) == L
    for l, r in enumerate(results):
        assert np.array_equal(quota[l], r.quota)


def test_make_scheduler_rejects_unknown_impl():
    with pytest.raises(ValueError):
        make_scheduler("simplex")


# --------------------------------------------------------------------------
# dispatch equivalence + engine integration (multi-device, slow)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_ep_forward_with_resched_sort_matches_onehot_multidevice():
    """Sort dispatch consuming a scheduler quota stack is bit-exact with
    the one-hot oracle on a (2, 4) mesh — counts, slots, drops, logits."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.core.duplication import duplicate_experts_host
        from repro.core.placement import stack_plans
        from repro.data.synthetic import skewed_distribution
        from repro.models.transformer import Runtime, forward, init_model
        from repro.schedule import make_scheduler

        base = get_config("mixtral-8x7b").reduced()
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rt = Runtime(mesh=mesh, ep=True, ep_ranks=4)
        m = base.moe
        B, S = 4, 32

        layers, plans = [], []
        sched = make_scheduler("greedy")
        quotas = []
        for l in range(base.num_layers):
            dist = skewed_distribution(m.num_experts, 3.0 + l)
            plan = duplicate_experts_host(dist, 4, m.duplication_slots,
                                          m.max_copies).plan
            plans.append(plan)
            counts = dist * B * S * m.top_k
            cap = (B * S // 4) * m.top_k * m.capacity_factor
            quotas.append(sched.plan_layer(
                counts, plan, ep_ranks=4, dup_slots=m.duplication_slots,
                cap=float(cap) * 4).quota)
        plan_stack = stack_plans(plans)
        resched = jnp.asarray(np.stack(quotas))

        out = {}
        runs = {}
        for impl in ("onehot", "sort"):
            cfg = dataclasses.replace(base, moe=dataclasses.replace(
                m, dispatch_impl=impl, capacity_factor=1.0))
            params = init_model(jax.random.PRNGKey(0), cfg)
            batch = {"tokens": jax.random.randint(
                jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)}
            logits, _, stats = jax.jit(
                lambda p, b, r, c=cfg: forward(p, c, b, rt, mode="train",
                                               plan=plan_stack, resched=r)
            )(params, batch, resched)
            runs[impl] = (logits, stats)
        la, sa = runs["onehot"]; lb, sb = runs["sort"]
        print(json.dumps({
            "logits_diff": float(jnp.abs(
                la.astype(jnp.float32) - lb.astype(jnp.float32)).max()),
            "counts_eq": bool(jnp.array_equal(sa["expert_counts"],
                                              sb["expert_counts"])),
            "slots_eq": bool(jnp.array_equal(sa["slot_counts"],
                                             sb["slot_counts"])),
            "dropped_a": int(np.asarray(sa["dropped"]).sum()),
            "dropped_b": int(np.asarray(sb["dropped"]).sum()),
            "moved": int(np.abs(np.asarray(sa["slot_counts"], np.int64)
                                ).sum()),
        }))
    """)
    assert res["counts_eq"]
    assert res["slots_eq"]
    assert res["dropped_a"] == res["dropped_b"]
    assert res["logits_diff"] < 1e-5, res["logits_diff"]


@pytest.mark.slow
def test_engine_reschedule_zero_drops_no_recompiles_multidevice():
    """Meshed ContinuousEngine, reschedule lever on, tight capacity:
    the rescue round + scheduler quotas absorb ALL capacity overflow
    (zero dropped tokens) and the lever never recompiles post-warmup."""
    res = run_sub("""
        import dataclasses
        from repro.configs.registry import get_config
        from repro.models.transformer import init_model
        from repro.serve import (ContinuousConfig, ContinuousEngine,
                                 ServeRequest)

        base = get_config("mixtral-8x7b").reduced()
        # cap floor is 8/rank (moe.dispatch.capacity), so the per-rank
        # token count must exceed it for capacity pressure to exist:
        # prefill_len=64 seq-sharded over 4 EP ranks = 16 tokens/rank,
        # constant prompts route them all to one expert, capf 0.5 -> cap 8
        cfg = dataclasses.replace(base, moe=dataclasses.replace(
            base.moe, capacity_factor=0.5, duplication_slots=1))
        params = init_model(jax.random.PRNGKey(0), cfg)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        out = {}
        for lever in ("duplicate", "reschedule"):
            ccfg = ContinuousConfig(max_slots=4, prefill_len=64,
                                    block_size=8, max_len=96,
                                    strategy="dist_only", lever=lever)
            eng = ContinuousEngine(cfg, params, ccfg, mesh=mesh, ep_ranks=4)
            eng.warmup()
            rng = np.random.default_rng(0)
            reqs = [ServeRequest(
                        rid=i,
                        tokens=np.full(int(rng.integers(40, 60)), 7,
                                       np.int32),
                        max_new_tokens=int(rng.integers(1, 6)),
                        arrival=float(i) * 0.01)
                    for i in range(10)]
            eng.run_trace(reqs)
            eng.assert_no_recompiles()
            s = eng.metrics.summary()
            out[lever] = {
                "completed": len(eng.scheduler.completed),
                "dropped": s.get("dropped_tokens", -1.0),
                "overflow": s.get("overflow_tokens", -1.0),
                "absorbed": s.get("overflow_absorbed_frac", -1.0),
                "a2a": s.get("resched_a2a_bytes", 0.0),
                "plans": s.get("resched_plans", 0.0),
            }
        print(json.dumps(out))
    """)
    dup, rs = res["duplicate"], res["reschedule"]
    assert dup["completed"] == 10 and rs["completed"] == 10
    # duplicate-only genuinely drops under this pressure...
    assert dup["dropped"] > 0, res
    # ...and the reschedule lever absorbs ALL of it: the rescue round sees
    # every round-1 overflow token and re-lands it within capacity
    assert rs["plans"] >= 1
    assert rs["overflow"] > 0, res
    assert rs["dropped"] == 0.0, res
    assert rs["absorbed"] == 1.0, res
    assert rs["a2a"] > 0, res
