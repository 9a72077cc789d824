"""Continuous-batching subsystem tests: KV block pool, scheduler
admission/eviction, BatchScheduler-compat property, paged-decode
correctness vs the synchronous reference, online GPS controller, and the
no-recompile-after-warmup guarantee."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.registry import get_config
from repro.models.transformer import Runtime, forward, init_cache, init_model
from repro.serve import (BatchScheduler, BlockAllocator, ContinuousConfig,
                         ContinuousEngine, ContinuousScheduler,
                         ControllerConfig, OnlineGPSController, Request,
                         ServeRequest)
from repro.serve.metrics import imbalance, plan_rank_loads
from repro.serve.scheduler import RequestState

KEY = jax.random.PRNGKey(0)


def _cfg():
    return get_config("mixtral-8x7b").reduced()


# --------------------------------------------------------------------------
# KV block allocator
# --------------------------------------------------------------------------

def test_block_allocator_alloc_free_roundtrip():
    a = BlockAllocator(num_blocks=9, block_size=4)
    assert a.free_blocks == 8                       # block 0 reserved
    got = a.alloc(5)
    assert len(got) == 5 and 0 not in got
    assert a.alloc(4) is None                       # all-or-nothing
    assert a.free_blocks == 3
    a.free(got)
    assert a.free_blocks == 8
    with pytest.raises(ValueError):
        a.free([0])                                 # null block protected


def test_block_allocator_blocks_for():
    a = BlockAllocator(num_blocks=4, block_size=8)
    assert a.blocks_for(1) == 1
    assert a.blocks_for(8) == 1
    assert a.blocks_for(9) == 2


# --------------------------------------------------------------------------
# continuous scheduler: admission / growth / eviction
# --------------------------------------------------------------------------

def _sched(max_slots=2, prefill_len=8, max_len=16, num_blocks=None,
           block_size=4, **kw):
    if num_blocks is None:
        num_blocks = 1 + max_slots * (max_len // block_size)
    alloc = BlockAllocator(num_blocks, block_size)
    return ContinuousScheduler(max_slots, prefill_len, max_len, alloc, **kw)


def _req(rid, plen=6, new=4, arrival=0.0):
    return ServeRequest(rid=rid, tokens=np.arange(plen, dtype=np.int32),
                        max_new_tokens=new, arrival=arrival)


def test_admission_respects_slots_and_arrival_times():
    s = _sched(max_slots=2)
    for i in range(3):
        s.submit(_req(i, arrival=float(i)))
    plan = s.schedule(now=0.0)
    assert [r.rid for r in plan.prefills] == [0]    # only rid 0 has arrived
    plan = s.schedule(now=5.0)
    assert [r.rid for r in plan.prefills] == [1]    # rid 2 waits for a slot
    assert s.request_in(0).rid == 0
    s.finish_slot(0, now=6.0)
    plan = s.schedule(now=6.0)
    assert [r.rid for r in plan.prefills] == [2]


def test_finish_frees_blocks_and_slot():
    s = _sched(max_slots=1)
    free0 = s.alloc.free_blocks
    s.submit(_req(0, plen=6))
    s.schedule(0.0)
    assert s.alloc.free_blocks == free0 - 2         # ceil(6/4) blocks
    req = s.finish_slot(0, 1.0)
    assert req.state == RequestState.FINISHED
    assert s.alloc.free_blocks == free0
    assert s.slots[0] is None


def test_decode_growth_allocates_block_on_boundary():
    s = _sched(max_slots=1, block_size=4)
    s.submit(_req(0, plen=4, new=4))
    plan = s.schedule(0.0)
    assert len(s.tables.owned[0]) == 1              # prompt fits one block
    s.ensure_decode_capacity(plan)                  # next write at pos 4
    assert len(s.tables.owned[0]) == 2


def test_pool_exhaustion_preempts_youngest():
    # pool of 3 usable blocks; two requests of 2 blocks each can't both run
    s = _sched(max_slots=2, prefill_len=8, max_len=12, num_blocks=4,
               block_size=4)
    s.submit(_req(0, plen=4, new=7, arrival=0.0))
    s.submit(_req(1, plen=4, new=7, arrival=0.1))
    plan = s.schedule(1.0)
    assert len(plan.prefills) == 2                  # both admitted (1 blk each)
    s.tables.lengths[:] = 4                         # both hit a block boundary
    s.ensure_decode_capacity(plan)
    # one grew, the other (younger rid 1) was preempted back to waiting
    assert [r.rid for r in plan.preempted] == [1]
    assert s.slots[1] is None and s.waiting[0].rid == 1
    assert s.waiting[0].n_preemptions == 1
    assert plan.decode_slots == [0]


def test_oversized_request_rejected():
    s = _sched(max_slots=1, prefill_len=8, max_len=16, num_blocks=3,
               block_size=4)
    with pytest.raises(ValueError):
        s.submit(_req(0, plen=8, new=8))            # needs 4 of 2 blocks


# --------------------------------------------------------------------------
# compatibility mode property: BatchScheduler semantics preserved
# --------------------------------------------------------------------------

@given(st.integers(0, 20), st.integers(1, 6), st.integers(1, 12),
       st.integers(1, 16))
@settings(max_examples=25, deadline=None)
def test_compat_fifo_matches_batch_scheduler(n_reqs, batch_size, seq_len,
                                             seed):
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(1, seq_len + 2)) for _ in range(n_reqs)]
    old = BatchScheduler(batch_size, seq_len)
    alloc = BlockAllocator(2 + n_reqs * seq_len, 4)
    new = ContinuousScheduler(batch_size, seq_len, 2 * seq_len, alloc,
                              compat_fifo=True)
    for rid, ln in enumerate(lens):
        toks = rng.integers(0, 100, size=ln).astype(np.int32)
        old.submit(Request(rid, toks.copy()))
        new.submit(ServeRequest(rid=rid, tokens=toks.copy()))
    while True:
        a, b = old.next_batch(), new.next_batch()
        if a is None or b is None:
            assert a is None and b is None
            break
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        np.testing.assert_array_equal(a["mask"], b["mask"])
        assert [r.rid for r in a["requests"]] == [r.rid for r in b["requests"]]


# --------------------------------------------------------------------------
# engine correctness: paged continuous decode == synchronous reference
# --------------------------------------------------------------------------

def _reference_generate(cfg, params, prompt, new_tokens):
    """Isolated greedy continuation: a single-slot engine serving exactly
    one request. Uses the same paged decode path as the engine under test
    (the fused kernel keeps attention scores in f32, so its logits differ
    from the linear-cache path by activation-dtype rounding — enough to
    flip greedy argmax near-ties on a random-init model; paged-vs-linear
    numerical agreement is covered by tolerance tests in
    tests/test_paged_attention.py)."""
    eng = ContinuousEngine(cfg, params, ContinuousConfig(
        max_slots=1, prefill_len=32, block_size=8, max_len=64,
        strategy="none", max_prefills_per_step=1))
    eng.warmup()
    eng.run_trace([ServeRequest(rid=0, tokens=np.asarray(prompt, np.int32),
                                max_new_tokens=new_tokens)])
    (done,) = eng.scheduler.completed
    return list(done.generated)


@pytest.fixture(scope="module")
def moe_model():
    cfg = _cfg()
    return cfg, init_model(KEY, cfg)


def test_paged_decode_matches_reference_multi_request(moe_model):
    """Requests of different lengths admitted at different times must each
    reproduce their isolated greedy continuation exactly."""
    cfg, params = moe_model
    prompts = [(np.arange(p, dtype=np.int32) * 13 + s) % cfg.vocab_size
               for s, p in enumerate((5, 11, 17))]
    refs = [_reference_generate(cfg, params, p, 5) for p in prompts]

    eng = ContinuousEngine(cfg, params, ContinuousConfig(
        max_slots=2, prefill_len=32, block_size=8, max_len=64,
        strategy="none", max_prefills_per_step=1))
    eng.warmup()
    reqs = [ServeRequest(rid=i, tokens=p, max_new_tokens=5,
                         arrival=0.0 if i < 2 else 0.01)
            for i, p in enumerate(prompts)]
    eng.run_trace(reqs)
    got = {r.rid: r.generated for r in eng.scheduler.completed}
    assert len(got) == 3
    for i, ref in enumerate(refs):
        assert got[i] == ref, (i, got[i], ref)


def test_preemption_recompute_is_deterministic(moe_model):
    """A starved pool forces preemption; greedy recompute must converge to
    the same outputs as an unconstrained run."""
    cfg, params = moe_model
    prompts = [(np.arange(9, dtype=np.int32) * 7 + s) % cfg.vocab_size
               for s in range(3)]

    outs, preempts = {}, {}
    for label, blocks in (("roomy", 0), ("starved", 10)):
        # starved: 9 usable blocks of 4; three 9-token prompts fill them,
        # and every request must still grow past position 12
        ccfg = ContinuousConfig(max_slots=3, prefill_len=16, block_size=4,
                                max_len=48, strategy="none",
                                num_blocks=blocks)
        eng = ContinuousEngine(cfg, params, ccfg)
        eng.warmup()
        reqs = [ServeRequest(rid=i, tokens=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        eng.run_trace(reqs)
        assert len(eng.scheduler.completed) == 3
        outs[label] = {r.rid: list(r.generated)
                       for r in eng.scheduler.completed}
        preempts[label] = sum(r.n_preemptions
                              for r in eng.scheduler.completed)
    assert preempts["starved"] > 0                  # starvation really hit
    assert outs["roomy"] == outs["starved"]


def test_no_recompilation_after_warmup(moe_model):
    cfg, params = moe_model
    eng = ContinuousEngine(cfg, params, ContinuousConfig(
        max_slots=4, prefill_len=32, block_size=8, max_len=64))
    eng.warmup()
    rng = np.random.default_rng(0)
    reqs = [ServeRequest(rid=i,
                         tokens=rng.integers(0, cfg.vocab_size,
                                             size=int(rng.integers(1, 30))
                                             ).astype(np.int32),
                         max_new_tokens=int(rng.integers(1, 8)),
                         arrival=float(i) * 0.01)
            for i in range(10)]
    eng.run_trace(reqs)
    assert len(eng.scheduler.completed) == 10
    eng.assert_no_recompiles()


def test_strategy_switch_does_not_recompile(moe_model):
    cfg, params = moe_model
    eng = ContinuousEngine(cfg, params, ContinuousConfig(
        max_slots=2, prefill_len=16, block_size=8, max_len=32,
        strategy="dist_only"))
    eng.warmup()
    for i, strat in enumerate(("none", "dist_only", "none")):
        eng.strategy = strat
        eng.replan()
        eng.run_trace([ServeRequest(rid=i, tokens=np.arange(
            6, dtype=np.int32), max_new_tokens=3)])
    eng.assert_no_recompiles()


def test_paged_decode_applies_sliding_window(moe_model):
    """Past the window boundary, paged decode must mask exactly like the
    linear windowed reference — and the mask must actually bind."""
    from repro.models import attention as attn
    cfg, params_model = moe_model
    p = jax.tree.map(lambda a: a[0], params_model["layers"])["attn"]
    rng = np.random.default_rng(0)
    B, S, K, hd = 1, 16, cfg.num_kv_heads, cfg.head_dim
    kv = rng.normal(size=(2, B, S, K, hd)).astype(np.float32) * 0.1
    x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)).astype(np.float32))
    pos = 10
    cache = {"k": jnp.asarray(kv[0]), "v": jnp.asarray(kv[1])}
    ref, _ = attn.gqa_decode_windowed(p, cfg, x, cache, pos, window=4)
    # same KV laid out as 4 paged blocks of 4 (pool block 0 = null)
    bs = 4
    pool = {n: jnp.zeros((6, bs, K, hd)).at[1:5].set(
        jnp.asarray(kv[i]).reshape(S // bs, bs, K, hd))
        for i, n in enumerate(("k", "v"))}
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    lengths = jnp.asarray([pos], jnp.int32)
    got, _ = attn.gqa_decode_paged(p, cfg, x, pool, table, lengths, window=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-3)
    unmasked, _ = attn.gqa_decode_paged(p, cfg, x, pool, table, lengths)
    assert not np.allclose(np.asarray(unmasked), np.asarray(ref),
                           rtol=2e-2, atol=2e-3)


def test_full_length_prompt_accepted_on_tight_pool():
    """A prompt of exactly max_len must be admissible: its single token
    comes from prefill logits and never writes KV (no +1 block)."""
    s = _sched(max_slots=1, prefill_len=8, max_len=8, num_blocks=3,
               block_size=4)
    s.submit(_req(0, plen=8, new=4))              # clamped to 1 new token
    plan = s.schedule(0.0)
    assert [r.rid for r in plan.prefills] == [0]
    assert s.slots[0].max_new_tokens == 1


# --------------------------------------------------------------------------
# token-weighted histograms
# --------------------------------------------------------------------------

def test_prefill_histogram_ignores_padding(moe_model):
    """Same prompt, different padding: weighted expert counts identical,
    and they sum to prompt_len * top_k per layer."""
    cfg, params = moe_model
    rt = Runtime(window_override=64)
    prompt = (np.arange(7, dtype=np.int32) * 3 + 1) % cfg.vocab_size
    counts = {}
    for S in (16, 32):
        toks = np.zeros((1, S), np.int32)
        toks[0, :7] = prompt
        tw = np.zeros((1, S), np.float32)
        tw[0, :7] = 1.0
        cache = init_cache(cfg, rt, 1, S)
        _, _, stats = forward(params, cfg, {"tokens": jnp.asarray(toks)},
                              rt, mode="prefill", cache=cache,
                              token_weight=jnp.asarray(tw))
        counts[S] = np.asarray(stats["expert_counts"])
    np.testing.assert_allclose(counts[16], counts[32], atol=1e-5)
    np.testing.assert_allclose(counts[16].sum(axis=-1),
                               7 * cfg.moe.top_k, atol=1e-5)


# --------------------------------------------------------------------------
# online GPS controller
# --------------------------------------------------------------------------

def _counts_with_skew(L, E, skew, total=1000.0):
    p_max = skew / E
    rest = (1.0 - p_max) / (E - 1)
    p = np.full((E,), rest)
    p[0] = p_max
    return np.tile(p * total, (L, 1))


def test_controller_switches_on_skew_shift():
    full = get_config("mixtral-8x7b")
    ctl = OnlineGPSController(
        full, ControllerConfig(window_iters=2, patience=1),
        predictor_available=True, initial_strategy="dist_only")
    L, E = full.num_layers, full.moe.num_experts
    decisions = []
    t = 0.0
    for skew in (1.5, 1.5, 3.2, 3.2, 3.2, 1.05, 1.05):
        for _ in range(2):
            t += 1.0
            d = ctl.observe(_counts_with_skew(L, E, skew), t)
            if d is not None:
                decisions.append(d)
    strategies = [d.strategy for d in decisions]
    assert "token_to_expert" in strategies          # high-skew window
    assert ctl.num_switches >= 1
    # measured skew is faithfully reported
    assert decisions[0].skew == pytest.approx(1.5, abs=0.01)


def test_controller_hysteresis_needs_patience():
    full = get_config("mixtral-8x7b")
    ctl = OnlineGPSController(
        full, ControllerConfig(window_iters=1, patience=3),
        predictor_available=True, initial_strategy="dist_only")
    L, E = full.num_layers, full.moe.num_experts
    d1 = ctl.observe(_counts_with_skew(L, E, 3.2), 1.0)
    assert d1.recommended == "token_to_expert" and not d1.switched
    d2 = ctl.observe(_counts_with_skew(L, E, 3.2), 2.0)
    assert not d2.switched
    d3 = ctl.observe(_counts_with_skew(L, E, 3.2), 3.0)
    assert d3.switched and d3.strategy == "token_to_expert"


def test_controller_skew_transfer():
    full = get_config("mixtral-8x7b")
    ctl = OnlineGPSController(
        full, ControllerConfig(window_iters=1, patience=1,
                               skew_cap_observed=2.0, skew_cap_target=4.0),
        predictor_available=True)
    # measured 1.9 on a cap-2.0 model ~ concentration 0.9 -> mapped 3.7:
    # well inside token_to_expert territory on the default (PCIe) hardware
    d = ctl.observe(_counts_with_skew(full.num_layers, 4, 1.9), 1.0)
    assert d.recommended == "token_to_expert"


# --------------------------------------------------------------------------
# metrics: plan-aware imbalance
# --------------------------------------------------------------------------

def test_plan_rank_loads_identity_vs_duplicated():
    from repro.core.duplication import duplicate_experts_host
    from repro.core.placement import stack_plans
    E, R, D = 8, 4, 1
    counts = _counts_with_skew(2, E, 3.0)
    home = plan_rank_loads(counts, None, R, 0)
    assert home.shape == (2, R)
    assert imbalance(home) > 1.5                    # skewed home placement
    plans = [duplicate_experts_host(counts[l] / counts[l].sum(), R, D, 4).plan
             for l in range(2)]
    dup = plan_rank_loads(counts, stack_plans(plans), R, D)
    assert imbalance(dup) < imbalance(home)         # duplication rebalances


# --------------------------------------------------------------------------
# workloads
# --------------------------------------------------------------------------

def test_arrival_processes_basic_properties():
    from repro.workloads import (bursty_arrivals, diurnal_arrivals,
                                 poisson_arrivals)
    rng = np.random.default_rng(0)
    for times in (poisson_arrivals(5.0, 50.0, rng),
                  bursty_arrivals(1.0, 20.0, 50.0, rng),
                  diurnal_arrivals(5.0, 0.8, 20.0, 50.0, rng)):
        assert (np.diff(times) >= 0).all()
        assert times.min() >= 0 and times.max() < 50.0
        assert len(times) > 10


def test_bursty_has_heavier_tail_than_poisson():
    rng = np.random.default_rng(1)
    from repro.workloads import bursty_arrivals, poisson_arrivals
    po = np.diff(poisson_arrivals(5.0, 400.0, rng))
    bu = np.diff(bursty_arrivals(0.5, 30.0, 400.0, rng))
    # burstiness: coefficient of variation of inter-arrival gaps > Poisson's
    cv = lambda g: g.std() / g.mean()
    assert cv(bu) > cv(po) * 1.2


def test_shifting_corpus_moves_concentration():
    from repro.workloads import ShiftingCorpus, Topic
    c = ShiftingCorpus(512, [Topic("flat", 0.3, 1.0, 1),
                             Topic("hot", 3.0, 0.05, 2)],
                       schedule=[(0.0, [1, 0]), (10.0, [0, 1])])
    rng = np.random.default_rng(0)
    def top_frac(t):
        toks = np.concatenate([c.sample_prompt(t, 64, rng)
                               for _ in range(30)])
        _, cnt = np.unique(toks, return_counts=True)
        return np.sort(cnt)[-5:].sum() / cnt.sum()
    assert top_frac(10.0) > top_frac(0.0) + 0.2     # late traffic concentrated
    np.testing.assert_allclose(c.mixture(5.0), [0.5, 0.5], atol=1e-9)


def test_trace_assembly_multi_tenant():
    from repro.workloads import (ShiftingCorpus, TenantSpec, Topic,
                                 make_trace, to_serve_requests)
    corp = ShiftingCorpus(256, [Topic("t", 1.0)], [(0.0, [1.0])])
    tenants = [TenantSpec("a", corp, rate=2.0, prompt_len_max=16),
               TenantSpec("b", corp, arrivals="bursty", rate=0.5,
                          burst_rate=8.0, prompt_len_max=32)]
    trace = make_trace(tenants, horizon=40.0, seed=0)
    assert len(trace) > 20
    assert all(trace[i].arrival <= trace[i + 1].arrival
               for i in range(len(trace) - 1))
    assert {r.tenant for r in trace} == {"a", "b"}
    assert all(1 <= len(r.tokens) <= 32 for r in trace)
    reqs = to_serve_requests(trace)
    assert reqs[0].rid == trace[0].rid


# --------------------------------------------------------------------------
# end-to-end: the benchmark in smoke mode IS the acceptance test
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_serve_traces_smoke():
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import bench_serve_traces
    summary, derived = bench_serve_traces.run(verbose=False, smoke=True)
    assert summary["completed"] > 0
    assert "completed=" in derived


# --------------------------------------------------------------------------
# one-rank expert parallelism: the one-chip serving configuration
# --------------------------------------------------------------------------

def test_one_by_one_mesh_engine_serves_through_ep_dispatch():
    """The one-chip deployment: bf16 weights placed by ``init_params`` on a
    1x1 Auto mesh, one EP rank, no replica slots. MoE runs through the EP
    dispatch (not the dense reference) and serves the same greedy tokens
    as the meshless engine on the same weights, with zero drops and zero
    compiles after warmup."""
    import dataclasses
    from repro.launch.mesh import make_dev_mesh
    from repro.models.transformer import init_params

    cfg = _cfg()
    # dropless capacity, as the one-chip smoke runs Mixtral
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=cfg.moe.num_experts / cfg.moe.top_k))
    mesh = make_dev_mesh(1, 1, devices=jax.devices()[:1])
    params = init_params(KEY, cfg, mesh=mesh)
    assert {a.dtype for a in jax.tree.leaves(params)} == {jnp.dtype("bfloat16")}
    ccfg = ContinuousConfig(max_slots=4, prefill_len=32, block_size=8,
                            max_len=64, strategy="none", dup_slots=0)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
               for n in rng.integers(5, 33, 6)]
    served = {}
    for name, m in (("ep", mesh), ("dense", None)):
        eng = ContinuousEngine(cfg, params, ccfg, mesh=m, ep_ranks=1)
        assert eng.rt.ep == (m is not None)
        eng.warmup()
        reqs = [ServeRequest(rid=i, tokens=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]
        now = 0.0
        for r in reqs:
            eng.submit(r)
        while eng.has_work():
            eng.step(now)
            now += 1.0
        eng.assert_no_recompiles()
        assert all(len(r.generated) == 6 for r in reqs)
        assert eng.metrics.resched["dropped_tokens"] == 0
        served[name] = [r.generated for r in reqs]
    assert served["ep"] == served["dense"]
