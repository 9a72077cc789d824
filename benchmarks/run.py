"""Benchmark harness: one entry per paper table/figure + serving traces.

Usage: PYTHONPATH=src python -m benchmarks.run [--smoke] [--json PATH] [name ...]

Prints a ``name,us_per_call,derived`` CSV line per benchmark, where
``derived`` is the benchmark's key reproduced quantity (see each module).

``--smoke``: seconds-scale configurations (exported to the bench modules
via ``REPRO_BENCH_SMOKE=1``) so CI can exercise every benchmark end to
end without reproducing the full figures.

``--json PATH``: additionally write a machine-readable result document
shared by all benches (the schema the CI bench-regression gate and the
BENCH_* trajectory tracking consume):

  {"schema": 2, "smoke": bool, "total_wall_s": float,
   "meta": {"git_sha": str, "timestamp_utc": str, "jax_version": str,
            "backend": str, "device_kind": str, "device_count": int,
            "python": str},
   "benches": {name: {"wall_us": float, "ok": bool, "derived": str,
                      "summary": {metric: number, ...} | null}}}

Benches whose ``run()`` returns a dict of scalars as its first element get
that dict embedded as ``summary``. ``benchmarks/bench_dispatch`` also
emits its own ``BENCH_dispatch.json`` phase-breakdown artifact.

``--history PATH``: append one JSONL line (``kind: "bench"``; meta +
total wall + per-bench wall/ok/summary metrics) per run — the trend
database ``repro.sweep.history`` reads back as per-(bench, metric,
config-key) series and ``benchmarks/check_trend.py`` scans for drift
(CI appends to ``benchmarks/history.jsonl`` and uploads it; sweep jobs
append ``kind: "sweep"`` lines to the same file).
"""

from __future__ import annotations

import json
import os
import platform
import subprocess
import sys
import time
from datetime import datetime, timezone


def _scalar_summary(obj):
    """First element of a bench's return value, kept only if it is a flat
    dict of JSON-safe scalars (the shared schema stores metrics, not blobs)."""
    if not isinstance(obj, dict):
        return None
    out = {}
    for k, v in obj.items():
        if isinstance(v, bool) or not isinstance(v, (int, float, str)):
            return None
        out[str(k)] = v
    return out


def run_meta() -> dict:
    """Provenance for a result document: without the commit + software +
    device identity a BENCH_*.json number cannot be compared across runs."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    meta = {
        "git_sha": sha,
        "timestamp_utc": datetime.now(timezone.utc).isoformat(
            timespec="seconds"),
        "python": platform.python_version(),
    }
    import jax
    dev = jax.devices()[0]
    meta.update(jax_version=jax.__version__,
                backend=jax.default_backend(),
                device_kind=dev.device_kind,
                device_count=jax.device_count())
    return meta


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--smoke" in argv:
        argv.remove("--smoke")
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_path = argv[i + 1]
        except IndexError:
            print("--json requires a PATH argument", file=sys.stderr)
            return 2
        del argv[i:i + 2]
    history_path = None
    if "--history" in argv:
        i = argv.index("--history")
        try:
            history_path = argv[i + 1]
        except IndexError:
            print("--history requires a PATH argument", file=sys.stderr)
            return 2
        del argv[i:i + 2]

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # import AFTER the env flag so modules can read it at import time too
    from benchmarks import (bench_appendix_c, bench_dispatch,
                            bench_dup_overhead, bench_fig4, bench_fig6,
                            bench_fig7, bench_migration,
                            bench_runtime_balance, bench_serve_traces,
                            bench_table1)
    benches = {
        "table1_skew_vs_error": bench_table1.run,
        "fig4_accuracy_overhead_perf": bench_fig4.run,
        "fig6_latency_breakdown": bench_fig6.run,
        "fig7_savings_vs_interconnect": bench_fig7.run,
        "sec5_duplication_overhead": bench_dup_overhead.run,
        "runtime_measured_balance": bench_runtime_balance.run,
        "appendix_c_generality": bench_appendix_c.run,
        "serve_traces_continuous": bench_serve_traces.run,
        "dispatch_phase_breakdown": bench_dispatch.run,
        "migration_store_vs_gather": bench_migration.run,
    }

    names = argv or list(benches)
    unknown = [n for n in names if n not in benches]
    if unknown:
        print(f"unknown benchmark(s): {', '.join(unknown)}", file=sys.stderr)
        print(f"available: {', '.join(benches)}", file=sys.stderr)
        return 2
    print("name,us_per_call,derived")
    failures = 0
    records = {}
    t_all = time.time()
    for name in names:
        fn = benches[name]
        t0 = time.time()
        try:
            first, derived = fn(verbose=True)
            us = (time.time() - t0) * 1e6
            print(f"{name},{us:.0f},{derived}")
            records[name] = {"wall_us": us, "ok": True,
                             "derived": str(derived),
                             "summary": _scalar_summary(first)}
        except Exception as e:      # keep the harness going
            failures += 1
            print(f"{name},FAILED,{type(e).__name__}: {e}")
            records[name] = {"wall_us": (time.time() - t0) * 1e6, "ok": False,
                             "derived": f"{type(e).__name__}: {e}",
                             "summary": None}
        sys.stdout.flush()
    meta = run_meta()
    total_wall_s = time.time() - t_all
    if json_path:
        doc = {
            "schema": 2,
            "smoke": os.environ.get("REPRO_BENCH_SMOKE") == "1",
            "total_wall_s": total_wall_s,
            "meta": meta,
            "benches": records,
        }
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {json_path}")
    if history_path:
        from repro.sweep.history import append_entry, bench_history_entry
        doc = {"smoke": os.environ.get("REPRO_BENCH_SMOKE") == "1",
               "total_wall_s": total_wall_s, "meta": meta,
               "benches": records}
        append_entry(history_path, bench_history_entry(doc))
        print(f"appended history to {history_path}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
