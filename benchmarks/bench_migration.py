"""Replica runtime benchmark: persistent store vs per-step pool gather.

Two quantities per (ep_ranks, dup_slots) point, measured on a real EP
mesh (8 fake host devices, spawned in a subprocess so the main process
keeps its single-device view):

* steady-state prefill step time with a FIXED duplicated plan — the
  ``replica_impl="gather"`` path pays the pool all_gather every step of
  every MoE layer, the ``"store"`` path reads resident slot weights;
  ``store_speedup = gather / store`` is the key derived quantity (the
  per-step overhead the paper's Sec 5 transfer model says should not
  exist at all).
* plan-switch stall — wall time of a full chunked migration between two
  different duplication plans, plus the bytes it moves (the one-off cost
  the store pays INSTEAD of the per-step collective).
* overlap on/off — the SAME plan switch executed synchronously (serving
  blocked while the diff drains) vs layer-staged and overlapped with
  prefill steps (``LayerStagedExecutor`` + the per-layer ready select in
  ``forward``): reports the serving-blocked wall seconds each path
  exposes, the steps-to-adopt, the modeled ``hidden_fraction`` of the
  transfer stall, and a bit-exactness check of the final store.

Writes ``BENCH_migration.json``; the CI gate fails when the store path is
slower than the gather path it replaces, when overlap hides less than
half the plan-switch stall, or when the async path diverges from the
synchronous one (``check_regression``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap


def _smoke() -> bool:
    return os.environ.get("REPRO_BENCH_SMOKE") == "1"


_SUB = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, math, time
import jax, jax.numpy as jnp, numpy as np
from repro.configs.registry import get_config
from repro.core.duplication import duplicate_experts_host
from repro.core.placement import stack_plans
from repro.data.synthetic import skewed_distribution
from repro.models.transformer import Runtime, forward, init_cache, init_model
from repro.runtime import (MigrationExecutor, ReplicaStore, migration_stall_s,
                           make_migrate_step, plan_diff)
from repro.train.steps import make_prefill_step

COMBOS = {combos}
ITERS = {iters}
B, S = 2, 64

def bench_point(ranks, dup):
    base = get_config("mixtral-8x7b").reduced()
    # heavy expert weights vs light token work: the regime where the
    # per-step pool gather dominates (weight bytes >> activation bytes)
    cfg = dataclasses.replace(base, num_layers=2, moe=dataclasses.replace(
        base.moe, d_ff_expert=2048, duplication_slots=dup))
    E = cfg.moe.num_experts
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8 // ranks, ranks), ("data", "model"))
    rt = Runtime(mesh=mesh, ep=True, ep_ranks=ranks, use_duplication=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    experts = params["layers"]["moe"]["experts"]
    batch = {{"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                           cfg.vocab_size)}}
    plan_a = stack_plans([duplicate_experts_host(
        skewed_distribution(E, 3.0 + l), ranks, dup, 4).plan
        for l in range(cfg.num_layers)])
    plan_b = stack_plans([duplicate_experts_host(
        skewed_distribution(E, 6.0 - l), ranks, dup, 4).plan
        for l in range(cfg.num_layers)])
    store = ReplicaStore.from_params(experts, plan_a, num_experts=E,
                                     ep_ranks=ranks, dup_slots=dup, mesh=mesh)
    cache = init_cache(cfg, rt, B, S)
    step = jax.jit(make_prefill_step(cfg, rt))

    def timed_pair(fa, fb):
        # best-of-ITERS, INTERLEAVED round by round so machine drift
        # (CPU contention, allocator state) hits both paths equally
        jax.block_until_ready(fa())               # compile + warm
        jax.block_until_ready(fb())
        best_a = best_b = math.inf
        for _ in range(ITERS):
            t0 = time.perf_counter()
            jax.block_until_ready(fa())
            best_a = min(best_a, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fb())
            best_b = min(best_b, time.perf_counter() - t0)
        return best_a, best_b

    with mesh:
        t_gather, t_store = timed_pair(
            lambda: step(params, batch, cache, plan_a),
            lambda: step(params, batch, cache, plan_a, None, store.weights))
        # plan switch: chunked migration A -> B (wall time of the fill)
        mig = make_migrate_step(mesh, num_experts=E, ep_ranks=ranks,
                                dup_slots=dup)
        diff = plan_diff(plan_a, plan_b, ranks, dup)
        t_switch, moved = 0.0, 0
        if diff.num_entries:
            ex = MigrationExecutor(mig, experts, store.entry_bytes, chunk=4)
            ex.begin(store.weights, diff, plan_b)
            ex._run_chunk()                       # compile the chunk step
            jax.block_until_ready(ex._back)
            ex.begin(store.weights, diff, plan_b)
            t0 = time.perf_counter()
            (weights, _, _), moved = ex.tick()
            jax.block_until_ready(weights)
            t_switch = time.perf_counter() - t0
    return dict(ranks=ranks, dup_slots=dup,
                gather_step_us=t_gather * 1e6, store_step_us=t_store * 1e6,
                store_speedup=t_gather / max(t_store, 1e-12),
                switch_entries=diff.num_entries, switch_bytes=int(moved),
                switch_wall_us=t_switch * 1e6)


def bench_overlap(ranks, dup):
    \"\"\"Same plan switch, synchronous vs overlapped: serving-blocked wall
    seconds, steps-to-adopt, modeled hidden fraction, bit-exactness.\"\"\"
    from repro.core.simulator import A100_PCIE
    from repro.runtime import (LayerStagedExecutor, migrate_all,
                               overlap_chunk_budget, split_hidden_exposed)
    base = get_config("mixtral-8x7b").reduced()
    cfg = dataclasses.replace(base, num_layers=2, moe=dataclasses.replace(
        base.moe, d_ff_expert=2048, duplication_slots=dup))
    E = cfg.moe.num_experts
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8 // ranks, ranks), ("data", "model"))
    rt = Runtime(mesh=mesh, ep=True, ep_ranks=ranks, use_duplication=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    experts = params["layers"]["moe"]["experts"]
    batch = {{"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                           cfg.vocab_size)}}
    plan_a = stack_plans([duplicate_experts_host(
        skewed_distribution(E, 3.0 + l), ranks, dup, 4).plan
        for l in range(cfg.num_layers)])
    plan_b = stack_plans([duplicate_experts_host(
        skewed_distribution(E, 6.0 - l), ranks, dup, 4).plan
        for l in range(cfg.num_layers)])
    store = ReplicaStore.from_params(experts, plan_a, num_experts=E,
                                     ep_ranks=ranks, dup_slots=dup, mesh=mesh)
    cache = init_cache(cfg, rt, B, S)
    step = jax.jit(make_prefill_step(cfg, rt))
    mig = make_migrate_step(mesh, num_experts=E, ep_ranks=ranks,
                            dup_slots=dup)
    diff = plan_diff(plan_a, plan_b, ranks, dup)
    entry = store.entry_bytes
    hw = A100_PCIE
    chunk = 4
    L = cfg.num_layers
    zeros_ready = jnp.zeros((L,), bool)

    with mesh:
        # warm everything: prefill (idle-overlap signature) + one chunk
        jax.block_until_ready(step(params, batch, cache, plan_a, None,
                                   store.weights, store.weights,
                                   zeros_ready, plan_a))
        ex = LayerStagedExecutor(mig, experts, entry, num_layers=L,
                                 chunk=chunk)
        ex.begin(store.weights, diff, plan_b)
        ex._run_chunk()
        jax.block_until_ready(ex._back)
        # baseline: migration-free step wall (the overlap window)
        t0 = time.perf_counter()
        jax.block_until_ready(step(params, batch, cache, plan_a, None,
                                   store.weights, store.weights,
                                   zeros_ready, plan_a))
        window = time.perf_counter() - t0

        # --- synchronous: serving BLOCKED while the whole diff drains
        sync_weights = migrate_all(mig, store.weights, experts, diff,
                                   chunk=chunk)
        ex.cancel()
        ex.begin(store.weights, diff, plan_b)
        t0 = time.perf_counter()
        (w_drain, _, _), _ = ex.tick()
        jax.block_until_ready(w_drain)
        sync_blocked = time.perf_counter() - t0

        # --- overlapped: chunks enqueued per step, serving never blocked;
        # the serving step reads (live, back, ready, target) per layer
        ex.cancel()
        ex.begin(store.weights, diff, plan_b)
        budget = overlap_chunk_budget(window, chunk_entries=chunk,
                                      entry_bytes=entry, hw=hw,
                                      max_chunks=1)   # stretch the drain
        steps = 0
        blocked = hidden_model = exposed_model = 0.0
        commit = None
        while commit is None and steps < 64:
            t0 = time.perf_counter()
            commit, moved = ex.tick(budget)       # enqueue only, no block
            blocked += time.perf_counter() - t0
            if moved:
                stall = moved / hw.link_bw
                h, e = split_hidden_exposed(stall, window)
                hidden_model += h
                exposed_model += e
            ready = (jnp.asarray(ex.ready_mask()) if ex.active
                     else jnp.ones((L,), bool))
            back = ex.back_weights if ex.active else store.weights
            tplan = plan_b if ex.active else plan_a
            out = step(params, batch, cache, plan_a, None, store.weights,
                       back, ready, tplan)
            jax.block_until_ready(out[0])         # serving critical path
            steps += 1
        weights, _, se = commit
        store.adopt(weights, se)
        bitexact = all(bool(jnp.array_equal(store.weights[k],
                                            sync_weights[k]))
                       for k in sync_weights)
    total = hidden_model + exposed_model
    # the GATED hidden fraction is MEASURED: how much of the serving-
    # blocked wall the synchronous drain pays does the overlapped path
    # avoid. (The modeled split is reported alongside but is 1.0 by
    # construction whenever the budget fits the window, so it cannot
    # catch an overlap regression — a tick that started blocking would.)
    measured = max(0.0, 1.0 - blocked / max(sync_blocked, 1e-12))
    return dict(ranks=ranks, dup_slots=dup,
                window_us=window * 1e6,
                sync_blocked_us=sync_blocked * 1e6,
                overlap_blocked_us=blocked * 1e6,
                steps_to_adopt=steps,
                hidden_fraction=measured,
                hidden_fraction_model=hidden_model / total if total else 1.0,
                bitexact=int(bitexact))

rows = [bench_point(r, d) for r, d in COMBOS]
overlap = bench_overlap(*COMBOS[0])
print(json.dumps({{"rows": rows, "overlap": overlap}}))
"""


def run(verbose: bool = True, smoke: bool = None):
    import repro

    if smoke is None:
        smoke = _smoke()
    combos = [(4, 1), (4, 2)] if smoke else [(4, 1), (4, 2), (8, 1), (8, 2)]
    iters = 5 if smoke else 10
    # repro is a namespace package (no __init__.py): locate src via __path__
    src_root = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    prog = textwrap.dedent(_SUB).format(combos=combos, iters=iters)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=1800,
                         env=dict(os.environ, PYTHONPATH=src_root,
                                  JAX_PLATFORMS="cpu"))
    if out.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{out.stderr[-2000:]}")
    doc_in = json.loads(out.stdout.strip().splitlines()[-1])
    rows, overlap = doc_in["rows"], doc_in["overlap"]

    doc = {"schema": 1, "smoke": smoke, "rows": rows, "overlap": overlap}
    out_dir = os.environ.get("REPRO_BENCH_OUT", ".")
    path = os.path.join(out_dir, "BENCH_migration.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)

    if verbose:
        print(f"{'ranks':>5s} {'dup':>4s} {'gather':>10s} {'store':>10s} "
              f"{'speedup':>8s} {'switch':>10s} {'moved':>10s}")
        for r in rows:
            print(f"{r['ranks']:5d} {r['dup_slots']:4d} "
                  f"{r['gather_step_us']:9.0f}us {r['store_step_us']:9.0f}us "
                  f"{r['store_speedup']:7.2f}x {r['switch_wall_us']:9.0f}us "
                  f"{r['switch_bytes'] / 1e6:8.1f}MB")
        o = overlap
        print(f"plan-switch overlap (ranks={o['ranks']} dup={o['dup_slots']}"
              f", window={o['window_us']:.0f}us):")
        print(f"  sync    blocked {o['sync_blocked_us']:9.0f}us  "
              f"steps_to_adopt=1")
        print(f"  overlap blocked {o['overlap_blocked_us']:9.0f}us  "
              f"steps_to_adopt={o['steps_to_adopt']}  "
              f"hidden={100 * o['hidden_fraction']:.0f}%  "
              f"bitexact={bool(o['bitexact'])}")
        print(f"wrote {path}")

    head = rows[0]
    summary = {
        "store_speedup": head["store_speedup"],
        "gather_step_us": head["gather_step_us"],
        "store_step_us": head["store_step_us"],
        "switch_wall_us": head["switch_wall_us"],
        "switch_bytes": float(head["switch_bytes"]),
        "min_store_speedup": min(r["store_speedup"] for r in rows),
        "overlap_hidden_fraction": overlap["hidden_fraction"],
        "overlap_hidden_fraction_model": overlap["hidden_fraction_model"],
        "overlap_steps_to_adopt": float(overlap["steps_to_adopt"]),
        "overlap_blocked_us": overlap["overlap_blocked_us"],
        "sync_blocked_us": overlap["sync_blocked_us"],
        "overlap_bitexact": float(overlap["bitexact"]),
    }
    derived = (f"store_speedup={head['store_speedup']:.2f}x "
               f"switch_stall={head['switch_wall_us']:.0f}us "
               f"moved={head['switch_bytes'] / 1e6:.1f}MB "
               f"overlap_hidden={100 * overlap['hidden_fraction']:.0f}%")
    return summary, derived


if __name__ == "__main__":
    run(verbose=True)
