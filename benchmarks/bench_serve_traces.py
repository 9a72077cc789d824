"""End-to-end trace-driven serving: continuous batching + online GPS.

Replays a bursty, skew-shifting request trace (repro.workloads) through
the continuous-batching engine with the online GPS controller attached,
on CPU with the dense reference MoE path. Reports SLO metrics (TTFT /
TPOT / p99 latency, goodput), per-window measured skew and the per-rank
load imbalance the engine's ACTIVE duplication plan would produce on a
4-rank EP deployment, and the controller's strategy-switch log.

Observability artifacts per run (repro.obs):
  * ``BENCH_serve_trace.json`` — merged Chrome trace-event JSON (local
    driver + meshed subprocess as separate process rows; open in
    Perfetto) with admission/prefill/decode/observe spans, the
    route/pack/a2a/ffn/combine dispatch-profile track, plan-switch and
    GPS-verdict instants, and migration begin/tick/commit spans;
  * ``BENCH_gps_audit.json`` — every controller verdict with the full
    input vector ``recommend_strategy`` saw.

Checked invariants (this benchmark doubles as the subsystem's
acceptance test — tests/test_continuous_serve.py calls ``run`` too):
  * every request in the trace completes;
  * the controller switches strategy at least once as the trace's topic
    mixture (and hence measured skew) shifts;
  * zero XLA recompilation after ``warmup()``;
  * the merged trace validates against the Chrome trace-event schema and
    contains the dispatch-phase + plan-switch spans (``trace_ok``);
  * the GPS audit log carries >= 1 verdict and the predictor-accuracy
    tracker scored >= 1 prediction window;
  * the DISABLED tracer costs < 1% of a meshed serving step
    (``tracer_off_overhead_frac`` — instrumentation is unconditional, so
    its off-mode cost is a hard budget, gated by ``check_regression``).

A second, MESHED smoke section (subprocess, 8 fake host devices) runs the
ContinuousEngine on a real EP mesh in store mode with overlapped
migration, and reports a step-time SLO column: ``meshed_step_p50_ms``
against ``meshed_slo_ms``, plus the backend-compile count after warmup.
``check_regression`` gates both (no recompiles, SLO met).

A FLEET A/B section (subprocess, same fake-device mesh) hosts TWO model
instances through `repro.fleet.FleetEngine` under the ``fleet_shift``
traffic-shift trace and compares a static equal HBM split against the
cross-model arbiter: the static leg must visibly violate the hot (chat)
tenant's TTFT SLO, the arbiter leg must commit >= 1 quota move and
recover fleet SLO attainment, and both legs must hold zero post-warmup
recompiles (every move is a logical quota inside compiled shapes).
Columns: ``fleet_slo_attainment`` (arbiter leg, lower-banded),
``fleet_slo_attainment_static`` (trend), ``fleet_arbiter_moves``
(lower-banded), ``fleet_step_p50_ms`` / ``fleet_recompiled`` (gated like
the meshed smoke).

A third, DECODE-HEAVY section replays the ``decode_heavy`` workload
(sparse arrivals, short prompts, long outputs -> a long steady decode
tail after warm prefill) through fused- and gather-``paged_attn_impl``
engines on identical state, and reports the decode fast path columns:
``decode_toks_per_s`` (wall-clock decode throughput, fused leg, gated
with a lower reference band), ``fused_vs_gather_speedup`` (the
attention-compute roofline: allocated table blocks the gather oracle
attends over / live blocks the fused kernel computes, measured from
real engine block-table state — structurally >= 1.0, asserted here and
gated by ``check_regression``), ``attn_phase_decode_us`` (decode-shaped
attn kernel phase, upper-banded), and trend-only interpret-mode walls
(``attn_fused_us``/``attn_gather_us``, ``decode_ab_ratio``) — raw
interpret-mode kernel timings are not meaningful perf references on
CPU, the roofline ratio is the portable signal.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time

import jax


def _smoke() -> bool:
    return os.environ.get("REPRO_BENCH_SMOKE") == "1"


# Step-time SLO for the meshed smoke deployment (p50, generous: CPU CI
# machines vary ~2x; a recompile-per-step regression blows through it by
# an order of magnitude, which is what the column is there to catch).
MESHED_SLO_MS = 2500.0

# Disabled-tracer budget: instrumentation is compiled in unconditionally,
# so with tracing OFF the per-step cost of all span/instant call sites
# must stay under 1% of a meshed serving step.
TRACER_OFF_BUDGET_FRAC = 0.01

# Conservative count of tracer call sites one engine step can hit (step,
# plan, admission, 2 x (prefill + prefill.sync), decode + its 4 parts,
# observe and record spans, migration tick span + begin/commit instants,
# plan/gps instants, boundary counters).
_TRACER_OPS_PER_STEP = 32

_MESHED_SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, numpy as np
from repro.configs.registry import get_config
from repro.models.transformer import init_model
from repro.obs import SpanTracer
from repro.serve import ContinuousConfig, ContinuousEngine
from repro.serve.scheduler import ServeRequest

from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("mixtral-8x7b").reduced()
params = init_model(jax.random.PRNGKey(0), cfg)
ccfg = ContinuousConfig(max_slots=4, prefill_len=32, block_size=16,
                        max_len=48, strategy="dist_only",
                        predict_interval=4, dup_slots=1, metrics_window=4)
tracer = SpanTracer(process_name="repro-serve-meshed")
eng = ContinuousEngine(cfg, params, ccfg, mesh=mesh, ep_ranks=4,
                       tracer=tracer)
eng.warmup()
rng = np.random.default_rng(0)
for i in range(6):
    eng.submit(ServeRequest(rid=i, arrival=0.0,
                            tokens=rng.integers(0, cfg.vocab_size,
                                                16).tolist(),
                            max_new_tokens=4))
walls = []
n = 0
while eng.has_work() and n < 40:
    t0 = time.perf_counter()
    eng.step(float(n))
    walls.append(time.perf_counter() - t0)
    n += 1
recompiled = 0
try:
    eng.assert_no_recompiles()
except AssertionError:
    recompiled = 1
eng.metrics.flush(eng._plan_stack, eng.ep_ranks, 1)
s = eng.metrics.summary()
trace_out = os.environ.get("REPRO_TRACE_OUT")
if trace_out:
    tracer.export(trace_out)
print(json.dumps({
    "step_p50_ms": float(np.percentile(walls, 50) * 1e3),
    "step_p99_ms": float(np.percentile(walls, 99) * 1e3),
    "iterations": n,
    "recompiled": recompiled,
    "completed": int(s["completed"]),
    "migration_commits": s["migration_commits"],
    "migration_hidden_s": s["migration_hidden_s"],
}))
"""


# Lever A/B under genuine capacity pressure: constant-token prompts
# concentrate routing on one expert, capacity_factor 0.5 with
# prefill_len 64 over 4 EP ranks puts the hot slot well past the cap
# floor (8/rank). The duplicate-only leg measurably DROPS tokens; the
# reschedule leg must absorb every overflow via the scheduler quotas +
# rescue dispatch round, paying only extra a2a bytes.
_RESCHED_SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, time
import jax, numpy as np
from repro.configs.registry import get_config
from repro.models.transformer import init_model
from repro.serve import ContinuousConfig, ContinuousEngine
from repro.serve.scheduler import ServeRequest

from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
base = get_config("mixtral-8x7b").reduced()
cfg = dataclasses.replace(base, moe=dataclasses.replace(
    base.moe, capacity_factor=0.5, duplication_slots=1))
params = init_model(jax.random.PRNGKey(0), cfg)
out = {}
for lever in ("duplicate", "reschedule"):
    ccfg = ContinuousConfig(max_slots=4, prefill_len=64, block_size=8,
                            max_len=96, strategy="dist_only",
                            predict_interval=4, dup_slots=1,
                            metrics_window=4, lever=lever)
    eng = ContinuousEngine(cfg, params, ccfg, mesh=mesh, ep_ranks=4)
    eng.warmup()
    rng = np.random.default_rng(0)
    for i in range(10):
        eng.submit(ServeRequest(rid=i, arrival=float(i) * 0.01,
                                tokens=np.full(int(rng.integers(40, 60)),
                                               7, np.int32),
                                max_new_tokens=int(rng.integers(1, 6))))
    walls, n = [], 0
    while eng.has_work() and n < 80:
        t0 = time.perf_counter()
        eng.step(float(n))
        walls.append(time.perf_counter() - t0)
        n += 1
    recompiled = 0
    try:
        eng.assert_no_recompiles()
    except AssertionError:
        recompiled = 1
    eng.metrics.flush(eng._plan_stack, eng.ep_ranks, 1)
    s = eng.metrics.summary()
    out[lever] = {
        "step_p50_ms": float(np.percentile(walls, 50) * 1e3),
        "completed": len(eng.scheduler.completed),
        "recompiled": recompiled,
        "dropped_tokens": float(s.get("dropped_tokens", -1.0)),
        "overflow_tokens": float(s.get("overflow_tokens", -1.0)),
        "overflow_absorbed_frac": float(
            s.get("overflow_absorbed_frac", -1.0)),
        "resched_a2a_bytes": float(s.get("resched_a2a_bytes", 0.0)),
        "resched_plans": float(s.get("resched_plans", 0.0)),
    }
print(json.dumps(out))
"""


# Fleet A/B under a traffic shift (fleet_shift workload: a chat tenant
# whose load ramps to 2x while a batch tenant stays flat). Both legs host
# the SAME two model instances on one 2x4 mesh with identical compiled
# shapes and a static equal KV split (12 of 24 pool blocks each, 1 of 2
# dup slots each); the arbiter leg may move quota between them, the
# static leg may not. The static split starves the chat model's KV share
# as the shift lands -> queued admissions -> TTFT SLO misses; the
# arbiter reads attainment/queue/skew pressure and moves KV-block (and
# dup-slot) quota toward it. All moves are quotas inside compiled
# shapes, so BOTH legs must hold zero post-warmup recompiles.
_FLEET_SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax, numpy as np
from repro.configs.registry import get_config
from repro.fleet import (ArbiterConfig, BATCH, FleetAdmission, FleetEngine,
                         FleetModelSpec, SLOClass)
from repro.models.transformer import init_model
from repro.serve import ContinuousConfig
from repro.sweep.workloads import build_workload
from repro.workloads import to_serve_requests

from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("mixtral-8x7b").reduced()
params = init_model(jax.random.PRNGKey(0), cfg)
ccfg = ContinuousConfig(max_slots=4, prefill_len=32, block_size=8,
                        max_len=48, strategy="dist_only",
                        predict_interval=4, dup_slots=2, metrics_window=4,
                        max_prefills_per_step=2)
trace = build_workload("fleet_shift", cfg.vocab_size, horizon=20.0,
                       rate=1.2, seed=0)
DT = 0.25
MAX_ITERS = 320

def run_leg(enable_arbiter):
    adm = FleetAdmission(
        routes={"chat": "m-chat", "batch": "m-batch"},
        slos={"chat": SLOClass("chat", slo_ttft=2.0, slo_tpot=1.0),
              "batch": BATCH})
    specs = [FleetModelSpec(n, cfg, params, ccfg,
                            dup_slot_quota=1, kv_block_quota=12)
             for n in ("m-chat", "m-batch")]
    fleet = FleetEngine(
        specs, mesh=mesh, ep_ranks=4, admission=adm,
        arbiter_cfg=ArbiterConfig(window_iters=8, patience=2,
                                  queue_norm=4.0, kv_blocks_per_move=4,
                                  kv_floor_blocks=4),
        enable_arbiter=enable_arbiter)
    fleet.warmup()
    for r in sorted(to_serve_requests(trace), key=lambda r: r.arrival):
        fleet.submit(r)
    now, n = 0.0, 0
    while fleet.has_work() and n < MAX_ITERS:
        fleet.step(now)
        now += DT
        n += 1
    recompiled = 0
    try:
        fleet.assert_no_recompiles()
    except AssertionError:
        recompiled = 1
    for eng in fleet.engines.values():
        eng.metrics.flush(eng._plan_stack, eng.ep_ranks,
                          eng.moe_cfg.duplication_slots)
    s = fleet.summary()
    return {
        "fleet_slo_attainment": s["fleet_slo_attainment"],
        "fleet_slo_attainment_worst": s["fleet_slo_attainment_worst"],
        "fleet_arbiter_moves": s["fleet_arbiter_moves"],
        "fleet_step_p50_ms": s["fleet_step_p50_ms"],
        "fleet_step_p99_ms": s["fleet_step_p99_ms"],
        "fleet_completed": s["fleet_completed"],
        "chat_attainment": adm.model_attainment(
            fleet.engines["m-chat"].metrics, "m-chat"),
        "batch_attainment": adm.model_attainment(
            fleet.engines["m-batch"].metrics, "m-batch"),
        "chat_kv_quota": s["m-chat_kv_block_quota"],
        "batch_kv_quota": s["m-batch_kv_block_quota"],
        "chat_dup_quota": s["m-chat_dup_slot_quota"],
        "recompiled": recompiled,
        "drained": float(not fleet.has_work()),
        "iterations": n,
        "moves": (fleet.arbiter.explain().splitlines()
                  if fleet.arbiter else []),
    }

out = {"submitted": len(trace),
       "static": run_leg(False), "arbiter": run_leg(True)}
print(json.dumps(out))
"""


def _run_fleet_ab(attempts: int = 2) -> dict:
    import repro
    src_root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    last = None
    for _ in range(attempts):
        try:
            out = subprocess.run(
                [sys.executable, "-c", textwrap.dedent(_FLEET_SUB)],
                capture_output=True, text=True, timeout=1500,
                env=dict(os.environ, PYTHONPATH=src_root,
                         JAX_PLATFORMS="cpu"))
        except subprocess.TimeoutExpired as e:
            last = f"timed out after {e.timeout}s"
            continue
        if out.returncode == 0:
            return json.loads(out.stdout.strip().splitlines()[-1])
        last = out.stderr[-2000:]
    raise RuntimeError(f"fleet A/B subprocess failed:\n{last}")


def _run_resched_ab(attempts: int = 2) -> dict:
    import repro
    src_root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    # the multi-device XLA CPU client rarely deadlocks at startup under a
    # fake-device mesh; a bounded timeout + one clean retry beats hanging
    # the whole bench suite on it
    last = None
    for _ in range(attempts):
        try:
            out = subprocess.run(
                [sys.executable, "-c", textwrap.dedent(_RESCHED_SUB)],
                capture_output=True, text=True, timeout=900,
                env=dict(os.environ, PYTHONPATH=src_root,
                         JAX_PLATFORMS="cpu"))
        except subprocess.TimeoutExpired as e:
            last = f"timed out after {e.timeout}s"
            continue
        if out.returncode == 0:
            return json.loads(out.stdout.strip().splitlines()[-1])
        last = out.stderr[-2000:]
    raise RuntimeError(f"resched A/B subprocess failed:\n{last}")


def _run_decode_heavy(cfg, params, smoke: bool) -> dict:
    """Fused-vs-gather paged-attention A/B on the decode_heavy workload:
    both engines replay the SAME trace, differing only in
    ``paged_attn_impl``. Emits the fused leg's wall-clock decode
    throughput and roofline ratio, the legs' throughput ratio, and an
    interleaved best-of kernel-level impl timing at the deployment's
    pool shapes."""
    import dataclasses

    from repro.moe.profile import attn_impl_times
    from repro.serve import ContinuousConfig, ContinuousEngine
    from repro.sweep.workloads import build_workload
    from repro.workloads import to_serve_requests

    horizon = 16.0 if smoke else 40.0
    trace = build_workload("decode_heavy", cfg.vocab_size,
                           horizon=horizon, rate=1.5, seed=0)
    ccfg = ContinuousConfig(max_slots=8, prefill_len=32, block_size=16,
                            max_len=96, strategy="none", metrics_window=8)
    legs = {}
    for impl in ("fused", "gather"):
        eng = ContinuousEngine(
            dataclasses.replace(cfg, paged_attn_impl=impl), params, ccfg)
        eng.warmup()
        eng.run_trace(to_serve_requests(trace), time_scale=20.0)
        eng.assert_no_recompiles()
        legs[impl] = eng.metrics.summary()
    ab = attn_impl_times(
        batch=ccfg.max_slots, num_kv=cfg.num_kv_heads,
        gqa=max(cfg.num_heads // cfg.num_kv_heads, 1),
        head_dim=cfg.head_dim, block_size=ccfg.block_size,
        max_blocks=ccfg.max_len // ccfg.block_size,
        window=cfg.sliding_window, iters=2 if smoke else 5)
    fused, gather = legs["fused"], legs["gather"]
    return {
        "decode_toks_per_s": fused.get("decode_toks_per_s", 0.0),
        "fused_vs_gather_speedup":
            fused.get("fused_vs_gather_speedup", 0.0),
        "decode_ab_ratio": (fused.get("decode_toks_per_s", 0.0)
                            / max(gather.get("decode_toks_per_s", 0.0),
                                  1e-9)),
        "attn_fused_us": ab["fused"] * 1e6,
        "attn_gather_us": ab["gather"] * 1e6,
        "decode_completed": fused["completed"],
        "decode_completed_gather": gather["completed"],
        "decode_trace_requests": float(len(trace)),
    }


def _run_meshed(trace_out: str) -> dict:
    import repro
    src_root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_MESHED_SUB)],
        capture_output=True, text=True, timeout=1800,
        env=dict(os.environ, PYTHONPATH=src_root, JAX_PLATFORMS="cpu",
                 REPRO_TRACE_OUT=trace_out))
    if out.returncode != 0:
        raise RuntimeError(
            f"meshed serve subprocess failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _tracer_off_overhead_frac(step_p50_s: float) -> float:
    """Microbenchmark the DISABLED tracer's per-call cost and scale it to
    one meshed serving step. A direct on/off A/B of full steps would be
    drowned by CI machine noise; the disabled path is pure Python with no
    shared state, so cost-per-op x sites-per-step is both stable and an
    upper bound (the estimate assumes every site fires every step)."""
    from repro.obs import SpanTracer
    off = SpanTracer(capacity=16, enabled=False)
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with off.span("x"):
            pass
    span_cost = (time.perf_counter() - t0) / n
    t0 = time.perf_counter()
    for _ in range(n):
        off.instant("x")
    inst_cost = (time.perf_counter() - t0) / n
    per_step = _TRACER_OPS_PER_STEP * max(span_cost, inst_cost)
    return per_step / max(step_p50_s, 1e-9)


def run(verbose: bool = True, smoke: bool = None):
    from repro.configs.registry import get_config
    from repro.core.predictors import ConditionalProbabilityModel
    from repro.core.simulator import A100_PCIE
    from repro.data.synthetic import make_routing_trace
    from repro.models.transformer import init_model
    from repro.obs import (SpanTracer, merge_traces, span_names,
                           validate_chrome_trace)
    from repro.serve import (ContinuousConfig, ContinuousEngine,
                             ControllerConfig, OnlineGPSController)
    from repro.workloads import skew_shift_trace, to_serve_requests

    if smoke is None:
        smoke = _smoke()
    cfg = get_config("mixtral-8x7b").reduced()
    full_cfg = get_config("mixtral-8x7b")      # controller simulates the
    params = init_model(jax.random.PRNGKey(0), cfg)   # production point

    horizon, rate = (24.0, 2.0) if smoke else (90.0, 1.5)
    trace = skew_shift_trace(cfg.vocab_size, horizon=horizon, rate=rate,
                             seed=0)

    # Token-to-Expert predictor (conditional-frequency ladder rung), fit on
    # a synthetic routing profile — its presence unlocks the t2e strategy.
    prof = make_routing_trace(num_sequences=32, seq_len=32,
                              vocab=cfg.vocab_size,
                              num_experts=cfg.moe.num_experts,
                              num_layers=cfg.num_layers, skew=1.8, seed=0)
    predictor = ConditionalProbabilityModel(
        cfg.num_layers, cfg.moe.num_experts, cfg.vocab_size
    ).fit(prof.experts, prof.tokens)

    controller = OnlineGPSController(
        full_cfg,
        ControllerConfig(
            hardware=A100_PCIE, window_iters=8, patience=1, min_saving=0.02,
            # skew is measured on the reduced smoke model but the guideline
            # is evaluated at the production point: transfer the scales
            skew_cap_observed=cfg.moe.num_experts / cfg.moe.top_k,
            skew_cap_target=full_cfg.moe.num_experts / full_cfg.moe.top_k),
        predictor_available=True, initial_strategy="dist_only")

    tracer = SpanTracer(process_name="repro-serve-local")
    ccfg = ContinuousConfig(max_slots=8, prefill_len=64, block_size=16,
                            max_len=96, strategy="dist_only",
                            predict_interval=4, dup_slots=1,
                            metrics_window=8)
    eng = ContinuousEngine(cfg, params, ccfg, ep_ranks=4,
                           predictor=predictor, controller=controller,
                           tracer=tracer)
    eng.warmup()
    end = eng.run_trace(to_serve_requests(trace), time_scale=20.0)
    eng.assert_no_recompiles()

    # prefill-shaped dispatch profile -> the phase_*_us columns, then
    # reset and re-profile at the decode batch shape -> decode_phase_*_us
    # (without reset_phases the second profile would double-accumulate)
    phases = eng.profile_phases(iters=2 if smoke else 5)
    s = eng.metrics.summary()
    eng.metrics.reset_phases()
    dec_phases = eng.profile_phases(iters=2 if smoke else 5,
                                    tokens=ccfg.max_slots)
    s.update({f"decode_phase_{k}_us": v * 1e6 for k, v in dec_phases.items()})

    n_completed = int(s["completed"])
    n_switches = controller.num_switches
    audit = controller.audit

    out_dir = os.environ.get("REPRO_BENCH_OUT", ".")
    with tempfile.TemporaryDirectory() as td:
        meshed_trace_path = os.path.join(td, "meshed_trace.json")
        meshed = _run_meshed(meshed_trace_path)
        with open(meshed_trace_path) as f:
            meshed_doc = json.load(f)
    resched_ab = _run_resched_ab()
    dup_leg, res_leg = resched_ab["duplicate"], resched_ab["reschedule"]
    decode_ab = _run_decode_heavy(cfg, params, smoke)
    fleet_ab = _run_fleet_ab()
    fleet_static, fleet_arb = fleet_ab["static"], fleet_ab["arbiter"]

    merged = merge_traces([tracer.to_chrome(), meshed_doc],
                          names=["repro-serve-local", "repro-serve-meshed"])
    merged["otherData"]["gps_audit"] = audit.to_obj()
    merged["otherData"]["pred_accuracy"] = eng.accuracy.to_obj()
    trace_path = os.path.join(out_dir, "BENCH_serve_trace.json")
    with open(trace_path, "w") as f:
        json.dump(merged, f)
    audit_path = os.path.join(out_dir, "BENCH_gps_audit.json")
    with open(audit_path, "w") as f:
        json.dump({"records": audit.to_obj(), "summary": audit.summary(),
                   "switches": [r.explain() for r in audit.switches]}, f,
                  indent=2)

    # schema + span-presence validation of the artifact CI uploads
    errors = validate_chrome_trace(merged)
    names = span_names(merged)
    required = {"attn", "route", "pack", "a2a", "ffn", "combine",
                "step", "plan.switch", "gps.decision"}
    if meshed["migration_commits"] > 0:
        required |= {"migration.tick", "migration.commit"}
    missing = sorted(required - names)
    trace_ok = float(not errors and not missing)

    overhead_frac = _tracer_off_overhead_frac(meshed["step_p50_ms"] / 1e3)

    s = dict(s,
             meshed_step_p50_ms=meshed["step_p50_ms"],
             meshed_step_p99_ms=meshed["step_p99_ms"],
             meshed_recompiled=float(meshed["recompiled"]),
             meshed_completed=float(meshed["completed"]),
             meshed_slo_ms=MESHED_SLO_MS,
             meshed_slo_ok=float(meshed["step_p50_ms"] <= MESHED_SLO_MS),
             trace_ok=trace_ok,
             trace_events=float(len(merged["traceEvents"])),
             tracer_off_overhead_frac=overhead_frac,
             # lever A/B at capacity pressure: duplicate-only drops, the
             # reschedule lever must absorb the same overflow dropless
             dup_dropped_tokens=dup_leg["dropped_tokens"],
             resched_dropped_tokens=res_leg["dropped_tokens"],
             overflow_tokens=res_leg["overflow_tokens"],
             overflow_absorbed_frac=res_leg["overflow_absorbed_frac"],
             resched_a2a_bytes=res_leg["resched_a2a_bytes"],
             resched_plans=res_leg["resched_plans"],
             resched_step_p50_ms=res_leg["step_p50_ms"],
             resched_recompiled=float(res_leg["recompiled"]
                                      or dup_leg["recompiled"]),
             # fleet A/B under traffic shift: static equal split vs
             # cross-model arbiter, two resident models on one mesh
             fleet_slo_attainment=fleet_arb["fleet_slo_attainment"],
             fleet_slo_attainment_static=fleet_static[
                 "fleet_slo_attainment"],
             fleet_arbiter_moves=fleet_arb["fleet_arbiter_moves"],
             fleet_step_p50_ms=fleet_arb["fleet_step_p50_ms"],
             fleet_chat_attainment=fleet_arb["chat_attainment"],
             fleet_chat_attainment_static=fleet_static["chat_attainment"],
             fleet_recompiled=float(fleet_arb["recompiled"]
                                    or fleet_static["recompiled"]),
             fleet_completed=fleet_arb["fleet_completed"],
             # decode fast path (decode_heavy fused/gather A/B legs);
             # attn_phase_decode_us is the decode-shaped attn kernel
             # phase from the dispatch re-profile above
             **decode_ab,
             attn_phase_decode_us=dec_phases.get("attn", 0.0) * 1e6,
             **{k: float(v) for k, v in audit.summary().items()},
             **{k: float(v) for k, v in eng.accuracy.summary().items()})

    if verbose:
        print(f"trace: {len(trace)} requests over {horizon:.0f}s (virtual), "
              f"served by {end:.1f}s | iterations={eng.iterations}")
        print(f"TTFT   p50={s['ttft_p50']*1e3:7.1f}ms  "
              f"p99={s['ttft_p99']*1e3:7.1f}ms")
        print(f"TPOT  mean={s['tpot_mean']*1e3:7.1f}ms  "
              f"p99={s['tpot_p99']*1e3:7.1f}ms")
        print(f"E2E    p50={s['latency_p50']*1e3:7.1f}ms  "
              f"p99={s['latency_p99']*1e3:7.1f}ms | "
              f"{s['throughput_tok_s']:.0f} tok/s, "
              f"{s['throughput_req_s']:.2f} req/s, "
              f"preemptions={int(s['preemptions'])}")
        print("\nwindow  t_end   skew  imbalance  strategy  "
              "pred_hit  pred_kl")
        for w in eng.metrics.windows:
            hit = f"{w.pred_hit_rate:8.2f}" if w.pred_hit_rate == \
                w.pred_hit_rate else "       -"
            kl = f"{w.pred_kl:7.3f}" if w.pred_kl == w.pred_kl else "      -"
            print(f"  {w.t_end:8.1f}s {w.skew:5.2f}  {w.imbalance:9.2f}  "
                  f"{w.strategy:16s} {hit} {kl}")
        print("\ncontroller switches:")
        for line in controller.switch_log():
            print("  " + line)
        print("\nGPS audit (last 4 verdicts of "
              f"{int(s['gps_verdicts'])}):")
        for line in audit.explain(last=4).splitlines():
            print("  " + line)
        if s.get("pred_windows", 0):
            print(f"\npredictor accuracy: {int(s['pred_windows'])} windows, "
                  f"hit_rate={s['pred_hit_rate']:.2f} "
                  f"kl={s['pred_kl']:.3f} l1={s['pred_l1']:.3f}")
        print(f"\nreplica migration: replans={int(s['migration_replans'])} "
              f"planned={s['migration_planned_bytes'] / 1e6:.2f}MB "
              f"moved={s['migration_bytes_moved'] / 1e6:.2f}MB "
              f"stall={s['migration_stall_us']:.0f}us "
              f"(hidden={s['migration_hidden_s']*1e6:.0f}us / "
              f"exposed={s['migration_exposed_s']*1e6:.0f}us) "
              f"rejected={int(s['migration_rejected'])} "
              f"prebegun={int(s['migration_prebegun'])} "
              f"cancelled={int(s['migration_cancelled'])}")
        print(f"meshed EP smoke: step p50={s['meshed_step_p50_ms']:.0f}ms "
              f"p99={s['meshed_step_p99_ms']:.0f}ms "
              f"(SLO {s['meshed_slo_ms']:.0f}ms -> "
              f"{'OK' if s['meshed_slo_ok'] else 'MISS'}), "
              f"recompiles={int(s['meshed_recompiled'])}, "
              f"completed={int(s['meshed_completed'])}")
        print(f"reschedule lever A/B (capf=0.5): duplicate drops "
              f"{dup_leg['dropped_tokens']:.0f} tok | reschedule drops "
              f"{res_leg['dropped_tokens']:.0f} of "
              f"{res_leg['overflow_tokens']:.0f} overflow "
              f"(absorbed={res_leg['overflow_absorbed_frac']:.2f}, "
              f"a2a={res_leg['resched_a2a_bytes'] / 1e6:.2f}MB, "
              f"plans={res_leg['resched_plans']:.0f}, "
              f"p50 {dup_leg['step_p50_ms']:.0f}ms -> "
              f"{res_leg['step_p50_ms']:.0f}ms)")
        print(f"fleet A/B (traffic shift, 2 models @ 2x4 mesh): "
              f"attainment static={fleet_static['fleet_slo_attainment']:.2f} "
              f"-> arbiter={fleet_arb['fleet_slo_attainment']:.2f} "
              f"(chat {fleet_static['chat_attainment']:.2f} -> "
              f"{fleet_arb['chat_attainment']:.2f}), "
              f"moves={int(fleet_arb['fleet_arbiter_moves'])}, "
              f"chat kv quota {int(fleet_static['chat_kv_quota'])} -> "
              f"{int(fleet_arb['chat_kv_quota'])} of 24, "
              f"dup quota -> {int(fleet_arb['chat_dup_quota'])}, "
              f"step p50={fleet_arb['fleet_step_p50_ms']:.0f}ms, "
              f"recompiles={int(s['fleet_recompiled'])}")
        for line in fleet_arb["moves"]:
            print("  " + line)
        print(f"decode fast path (decode_heavy A/B): "
              f"{decode_ab['decode_toks_per_s']:.0f} decode tok/s, "
              f"roofline fused_vs_gather="
              f"{decode_ab['fused_vs_gather_speedup']:.2f}x "
              f"(alloc/live blocks), "
              f"attn phase decode={s['attn_phase_decode_us']:.0f}us | "
              f"interpret-mode walls (trend only): "
              f"fused={decode_ab['attn_fused_us']:.0f}us "
              f"gather={decode_ab['attn_gather_us']:.0f}us "
              f"ab_ratio={decode_ab['decode_ab_ratio']:.2f}")
        print(f"trace artifact: {trace_path} "
              f"({int(s['trace_events'])} events, "
              f"{'valid' if trace_ok else 'INVALID: ' + '; '.join(errors[:3] + missing)}) | "
              f"gps audit: {audit_path} | "
              f"tracer-off overhead={overhead_frac:.2e} of a meshed step "
              f"(budget {TRACER_OFF_BUDGET_FRAC:.0%})")
        if phases:
            print("\ndispatch phase breakdown (prefill vs decode shape, "
                  f"impl={eng.moe_cfg.dispatch_impl}):")
            total = phases.get("total", 0.0) or 1.0
            for k in ("route", "pack", "a2a", "ffn", "combine"):
                print(f"  {k:8s} {phases[k]*1e6:9.0f}us "
                      f"({100.0 * phases[k] / total:4.1f}%)  "
                      f"decode {dec_phases[k]*1e6:9.0f}us")
            if "attn" in phases:
                print(f"  {'attn':8s} {phases['attn']*1e6:9.0f}us "
                      f"(paged decode kernel, impl="
                      f"{getattr(cfg, 'paged_attn_impl', 'fused')})  "
                      f"decode {dec_phases['attn']*1e6:9.0f}us")
            if "migrate" in phases:
                print(f"  {'migrate':8s} {phases['migrate']*1e6:9.0f}us "
                      "(per plan-switch chunk, not per step)")
            if "prefetch" in phases:
                print(f"  {'prefetch':8s} {phases['prefetch']*1e6:9.0f}us "
                      "(overlapped-fill issue cost on the critical path)")

    assert n_completed == len(trace), (n_completed, len(trace))
    if not smoke:
        assert n_switches >= 1, "controller never switched strategy"
    assert len(audit) >= 1, "GPS audit log recorded no verdicts"
    assert s.get("pred_windows", 0) >= 1, \
        "predictor-accuracy tracker scored no windows"
    assert trace_ok == 1.0, \
        f"trace artifact invalid: {errors[:5]} missing={missing}"
    assert overhead_frac < TRACER_OFF_BUDGET_FRAC, (
        f"disabled tracer costs {overhead_frac:.1%} of a meshed step "
        f"(budget {TRACER_OFF_BUDGET_FRAC:.0%})")
    # the combined strategy space's acceptance: under identical capacity
    # pressure the reschedule lever beats duplicate-only — it sees real
    # overflow yet drops nothing, where the duplicate leg drops tokens
    assert dup_leg["dropped_tokens"] > 0, \
        "duplicate leg saw no drops — capacity pressure recipe broken"
    assert res_leg["overflow_tokens"] > 0, \
        "reschedule leg saw no overflow — lever never engaged"
    assert res_leg["dropped_tokens"] == 0.0, (
        f"reschedule lever dropped {res_leg['dropped_tokens']:.0f} of "
        f"{res_leg['overflow_tokens']:.0f} overflow tokens")
    assert s["resched_recompiled"] == 0.0, \
        "lever A/B legs recompiled after warmup"
    # decode fast path acceptance: both A/B legs must finish the whole
    # decode-heavy trace, the fused leg must show real decode throughput,
    # and the roofline ratio is structurally >= 1.0 (the gather view can
    # never cover fewer blocks than are live)
    assert decode_ab["decode_completed"] \
        == decode_ab["decode_trace_requests"] \
        == decode_ab["decode_completed_gather"], decode_ab
    assert decode_ab["decode_toks_per_s"] > 0, \
        "decode_heavy trace produced no pure-decode iterations"
    assert decode_ab["fused_vs_gather_speedup"] >= 1.0, (
        f"attention roofline ratio "
        f"{decode_ab['fused_vs_gather_speedup']:.3f} < 1.0 — live-block "
        f"accounting is broken")
    # fleet A/B acceptance: the static equal split must visibly violate
    # the hot tenant's SLO, the arbiter leg must commit >= 1 move and
    # recover attainment, and neither leg may recompile after warmup
    assert fleet_static["chat_attainment"] < 0.9, (
        f"static split never starved the chat tenant "
        f"(attainment {fleet_static['chat_attainment']:.2f}) — the fleet "
        f"A/B pressure recipe is broken")
    assert fleet_arb["fleet_arbiter_moves"] >= 1, \
        "arbiter committed no moves under a sustained traffic shift"
    assert fleet_arb["fleet_slo_attainment"] \
        > fleet_static["fleet_slo_attainment"], (
        f"arbiter leg did not beat the static split: "
        f"{fleet_arb['fleet_slo_attainment']:.2f} vs "
        f"{fleet_static['fleet_slo_attainment']:.2f}")
    assert fleet_static["drained"] and fleet_arb["drained"], fleet_ab
    assert s["fleet_recompiled"] == 0.0, \
        "fleet legs recompiled after warmup — a quota move changed shapes"

    derived = (f"completed={n_completed}/{len(trace)} "
               f"switches={n_switches} "
               f"verdicts={int(s['gps_verdicts'])} "
               f"pred_hit={s.get('pred_hit_rate', float('nan')):.2f} "
               f"ttft_p99={s['ttft_p99']*1e3:.0f}ms "
               f"tpot_p99={s['tpot_p99']*1e3:.0f}ms "
               f"meshed_p50={s['meshed_step_p50_ms']:.0f}ms "
               f"resched_absorbed={s['overflow_absorbed_frac']:.2f} "
               f"decode_tok_s={s['decode_toks_per_s']:.0f} "
               f"attn_roofline={s['fused_vs_gather_speedup']:.2f}x "
               f"fleet_slo={s['fleet_slo_attainment_static']:.2f}->"
               f"{s['fleet_slo_attainment']:.2f} "
               f"(moves={int(s['fleet_arbiter_moves'])})")
    return s, derived


if __name__ == "__main__":
    run(verbose=True)
