"""Benchmark harness: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<file>.json``) and a
traffic mix (``bench/traffic/<mix>.json``). The configuration names its
architecture by the published config's ``model_type``, and
``bench/arch/<model_type>.py`` holds what is that architecture's alone
(see ``load_arch``). A run:

1. enables JAX's persistent compilation cache at its fixed path
   (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``);
2. exits non-zero, printing no result, without a TPU or with fewer chips
   than the cell asks for;
3. makes the weights on the device from the seed (``weights.py``) and
   builds one ``ContinuousEngine``, warmed for the cell's shapes;
4. drives ``submit``/``step`` for ``--seconds``: an open loop submits each
   request at its due time, a closed loop keeps every client's next
   request in as soon as its last one finishes;
5. frees the program, then checks a sample of the finished requests,
   spread over the decode slots, against the float32 reference
   (``reference.py``);
6. prints the result as one JSON line, the last line of stdout.

With ``--trace 1`` the window runs under the JAX profiler and the line
carries the cell's per-layer metrics (each read by
``bench/metrics/<name>.py`` from the reduced trace, the engine's spans
and the harness's own records) instead of the end-to-end ones. A metric
``<name>.<cells>`` without a file of its own is read by ``<name>.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
ARCH = os.path.join(BENCH, "arch")
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

import traffic as T  # noqa: E402

# served tokens the reference checks: the first ones of a request drawn
# for each decode slot, and the last ones of the longest request
SAMPLE_PREFIX = 64
SAMPLE_TAIL = 128


# ------------------------------------------------------------------ spec

def load_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(spec: dict, workload: str, arch_dir: str = ARCH):
    """(cell, configuration file contents, traffic mix, architecture
    module) of a cell."""
    cell = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        conf = json.load(f)
    mix = None
    if os.path.exists(os.path.join(BENCH, "traffic",
                                   f"{cell['traffic']}.json")):
        mix = T.load_mix(cell["traffic"])
    return cell, conf, mix, load_arch(conf, arch_dir)


def load_arch(conf: dict, arch_dir: str = ARCH):
    """The architecture module of a configuration: ``<arch_dir>/
    <model_type>.py``, loaded by path (once per path and process).

    It holds what is that architecture's alone: ``PUBLISHED`` (the
    published widths of each ``source``), ``TENSORS`` (a tensor's index
    there seeds its weights), ``dims(conf)`` (sizes under short names,
    ``V`` the vocabulary), ``model_config(conf, engine)`` (the program's
    ``ModelConfig``), ``serving_tree(root, D)`` and ``outer_tensors(root,
    D)`` (drawn by ``weights.draw``), ``forward_layers(conf, root, xs,
    seqs, control)`` (the reference's layers) and the counts
    ``expert_bytes``, ``fixed_bytes``, ``kv_read_bytes``,
    ``prefill_flops`` and ``decode_flops``."""
    mt = conf.get("model_type")
    named = isinstance(mt, str) and re.fullmatch(r"\w+", mt)
    path = os.path.join(arch_dir, f"{mt if named else '<model_type>'}.py")
    shown = os.path.relpath(path, ROOT) if path.startswith(ROOT) else path
    if not (named and os.path.isfile(path)):
        raise SystemExit(f"configuration {conf.get('name')!r} (model_type "
                         f"{mt!r}) needs its architecture module {shown}")
    name = f"bench_arch_{mt}"
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != path:
        sp = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod


def per_layer_metrics(spec: dict, workload: str) -> List[dict]:
    reported = {m["name"] for m in spec["end_to_end"]
                if workload in m.get("workloads", [workload])}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def reader_path(name: str) -> str:
    """``metrics/<name>.py``, else the reader of the name's first part."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(BENCH, "metrics", f"{name.split('.')[0]}.py")
    return path


def load_reader(name: str):
    path = reader_path(name)
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------- model

def model_config(conf: dict, arch_dir: str = ARCH):
    """The program's ``ModelConfig`` for a configuration file."""
    return load_arch(conf, arch_dir).model_config(conf, conf["engine"])


def engine_config(conf: dict, mix: dict):
    """``ContinuousConfig`` from the mix's slots and every key of the
    configuration's ``engine`` that it has."""
    from repro.serve import ContinuousConfig
    names = {f.name for f in dataclasses.fields(ContinuousConfig)}
    kw = {k: v for k, v in conf["engine"].items() if k in names}
    return ContinuousConfig(**kw, **mix["slots"])


# -------------------------------------------------------------- records

@dataclass
class Step:
    """One engine step: host times (s, window clock), prompt lengths it
    prefilled, the context of every token it decoded, and the program's
    expert counts (L, E) (kept as the engine's array, read on the host
    only after the window)."""
    t0: float
    t1: float
    prefills: List[int] = field(default_factory=list)
    decodes: List[int] = field(default_factory=list)
    counts: Optional[np.ndarray] = None


@dataclass
class Req:
    index: int
    prompt: np.ndarray
    due: float
    submit: Optional[float] = None
    times: List[float] = field(default_factory=list)
    sr: object = None                 # the engine's ServeRequest
    slot: Optional[int] = None        # the decode slot it was admitted to
    finished: bool = False

    @property
    def served(self) -> list:
        return list(self.sr.generated)


def recorder_class():
    """ServeMetrics that also keeps each iteration's expert counts, for the
    per-layer readers."""
    from repro.serve.metrics import ServeMetrics

    class Recorder(ServeMetrics):
        def __init__(self):
            super().__init__()
            self.log = []

        def record_iteration(self, now, dt, **kw):
            self.log.append(kw.get("counts"))
            super().record_iteration(now, dt, **kw)

    return Recorder


class Context:
    """What a per-layer reader gets: the configuration (``conf``, its
    architecture module ``arch``, whose counts the readers call, and its
    sizes ``D``), the mix, ``chips``, the device's ``peak`` row, the
    window's ``steps`` and ``requests``, the engine's ``spans`` on the
    trace's clock, the reduced ``trace`` (None without one) and
    ``block_size``.
    The traced run traces all of its window."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


# ---------------------------------------------------------------- serve

class ServeLoop:
    """Drives one engine with one traffic mix. Times are seconds on the
    window's clock: ``t0`` (``perf_counter_ns``) is the window's start, and
    whatever happened in set-up lies before 0.

    A closed loop fills its clients' slots in set-up (``fill``): each
    client's first request is admitted and has its first token before the
    window opens, so the window measures the loop's steady traffic and not
    its start. Every finished request is followed at once by the client's
    next one. An open loop submits each request at its due time."""

    def __init__(self, engine, gen: T.Traffic, mix: dict, recorder):
        self.engine, self.gen, self.mix, self.recorder = \
            engine, gen, mix, recorder
        self.open_loop = mix["loop"] == "open"
        self.reqs: List[Req] = []
        self.live = {}
        self.steps: List[Step] = []
        self.failed = 0
        self.t0 = time.perf_counter_ns()

    def now(self) -> float:
        return (time.perf_counter_ns() - self.t0) * 1e-9

    def submit(self, due: float):
        from repro.serve.scheduler import ServeRequest
        r = self.gen.request(len(self.reqs))
        rec = Req(r.index, r.prompt, due)
        self.reqs.append(rec)
        sr = ServeRequest(rid=r.index, tokens=r.prompt,
                          max_new_tokens=r.max_new, arrival=due)
        rec.sr = sr
        try:
            self.engine.submit(sr)
        except ValueError as e:
            self.failed += 1
            print(f"request {r.index} refused: {e}", file=sys.stderr)
            return
        rec.submit = self.now()
        self.live[r.index] = rec

    def step(self, k: int, trace: bool) -> Step:
        import jax
        eng = self.engine
        n_log = len(self.recorder.log)
        ts = self.now()
        if trace:
            with jax.profiler.TraceAnnotation("bench.step", step=k):
                ev = eng.step(ts, clock=self.now)
        else:
            ev = eng.step(ts, clock=self.now)
        te = self.now()
        st = Step(ts, te)
        if len(self.recorder.log) > n_log:
            st.counts = self.recorder.log[-1]
        active = [r for r in eng.scheduler.slots if r is not None]
        for sr in active:
            rec = self.live[sr.rid]
            rec.slot = sr.slot if rec.slot is None else rec.slot
        for sr in active + ev.completed:
            rec = self.live[sr.rid]
            n = len(sr.generated)
            for j in range(len(rec.times), n):
                rec.times.append(te)
                if j == 0:
                    st.prefills.append(len(rec.prompt))
                else:
                    st.decodes.append(len(rec.prompt) + j)
        for sr in ev.completed:
            self.live.pop(sr.rid).finished = True
            if not self.open_loop:
                self.submit(self.now())
        return st

    def fill(self):
        """Closed loop: admit every client's first request (set-up)."""
        if self.open_loop:
            return
        for _ in range(self.mix["clients"]):
            self.submit(self.now())
        k = 0
        while self.engine.scheduler.waiting:
            self.step(k, False)
            k += 1

    def window(self, seconds: float, trace: bool) -> float:
        """Serve for ``seconds`` from now; returns the end of the last
        step."""
        shift = self.now()
        self.t0 += int(shift * 1e9)
        for r in self.reqs:
            r.due -= shift
            r.submit = None if r.submit is None else r.submit - shift
            r.times = [t - shift for t in r.times]
        due = (T.arrival_times(self.mix["arrivals"], seconds)
               if self.open_loop else [])
        nxt = k = 0
        while True:
            t = self.now()
            if t >= seconds:
                break
            if self.open_loop:
                while nxt < len(due) and due[nxt] <= t:
                    self.submit(float(due[nxt]))
                    nxt += 1
                if not self.engine.has_work():
                    wake = due[nxt] if nxt < len(due) else seconds
                    time.sleep(max(0.0, min(wake, seconds) - self.now()))
                    continue
            self.steps.append(self.step(k, trace))
            k += 1
        return self.now()


def end_to_end(reqs: List[Req], end: float, chips: int) -> dict:
    """Over the window [0, end]: TTFT of every request due in it (one still
    without a first token counts as waiting until the window's end), the
    gaps between consecutive output tokens both served in it, and its
    output tokens per second per chip."""
    ttft = [(r.times[0] if r.times else end) - r.due
            for r in reqs if 0 <= r.due <= end]
    gaps = np.concatenate([np.diff([t for t in r.times if t >= 0])
                           for r in reqs] or [np.zeros(0)])
    tokens = sum(1 for r in reqs for t in r.times if 0 <= t <= end)

    def ms(x):
        return {"value": float(x) * 1e3, "unit": "ms"}

    return {"ttft_p50_ms": ms(np.percentile(ttft, 50)),
            "ttft_p95_ms": ms(np.percentile(ttft, 95)),
            "itl_p95_ms": ms(np.percentile(gaps, 95)),
            "out_tok_s_per_chip": {"value": tokens / end / chips,
                                   "unit": "tokens/s"}}


@dataclass
class Pick:
    """A finished request the reference checks: it is fed the prompt and
    the first ``fed`` served tokens, and checks the last ``checked`` of
    those."""
    req: Req
    fed: int
    checked: int


def sample(reqs: List[Req], seed: int) -> List[Pick]:
    """Finished requests for the reference, spread over the decode slots:
    the longest one, checked on its last SAMPLE_TAIL served tokens (the
    deepest contexts of the window), and for every other slot that
    finished a request, one of them drawn from the seed, checked on its
    first SAMPLE_PREFIX."""
    done = [r for r in reqs if r.finished and r.served and r.slot is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.served), r.index))
    n = len(longest.served)
    out = [Pick(longest, n, min(SAMPLE_TAIL, n))]
    by_slot = {}
    for r in done:
        if r.slot != longest.slot:
            by_slot.setdefault(r.slot, []).append(r)
    rng = np.random.default_rng([seed, 4])
    for slot in sorted(by_slot):
        group = by_slot[slot]
        r = group[int(rng.integers(len(group)))]
        n = min(SAMPLE_PREFIX, len(r.served))
        out.append(Pick(r, n, n))
    return out


def slots_per_half(picked: List[Pick], max_slots: int) -> int:
    """Distinct decode slots checked in the emptier half of the batch."""
    slots = {p.req.slot for p in picked}
    lower = sum(s < max_slots // 2 for s in slots)
    return min(lower, len(slots) - lower)


def gap_stats(gaps: np.ndarray) -> dict:
    """Readings of a gap array: the widest gap, its 99th percentile, its
    mean, and the share of served tokens that are not the reference's top
    token."""
    if not len(gaps):
        return dict.fromkeys(("logit_gap", "logit_gap_p99", "logit_gap_mean",
                              "mismatch_share"))
    return {"logit_gap": float(gaps.max()),
            "logit_gap_p99": float(np.percentile(gaps, 99)),
            "logit_gap_mean": float(gaps.mean()),
            "mismatch_share": float((gaps > 0).mean())}


def check(arch, conf: dict, seed: int, picked: List[Pick], extra: dict,
          control: bool = False):
    """Numbers compared, each as (value, limit, passes), and every gap
    reading. The configuration's ``limits`` say which gap readings are
    compared (each an upper limit). With ``control`` the fp8 control takes
    the program's place: the tokens it ranks first at the same positions
    are judged, and the program's own readings come back under
    ``program_<name>``."""
    import reference
    lim = conf["limits"]
    seqs = [np.concatenate([p.req.prompt,
                            np.asarray(p.req.served[:p.fed], np.int32)])
            for p in picked]
    out = reference.teacher_forced(arch, conf, seed, seqs,
                                   [p.checked for p in picked],
                                   control=control)
    readings = gap_stats(out["control_gap" if control else "gap"])
    checks = {name: (v, lim[name], v is not None and v <= lim[name])
              for name, v in readings.items() if name in lim}
    if control:
        readings.update({f"program_{k}": v
                         for k, v in gap_stats(out["gap"]).items()})
    n = len(out["gap"])
    checks["served_tokens"] = (n, lim["served_tokens_min"],
                               n >= lim["served_tokens_min"])
    checks.update(extra)
    return checks, readings


# ------------------------------------------------------------------ run

def build(arch, conf: dict, mix: dict, seed: int, devices, trace: bool):
    """Weights from the seed and one warm engine on ``devices``. Returns
    (engine, recorder, tracer)."""
    import jax

    import weights as W
    from repro.launch.mesh import make_dev_mesh
    from repro.obs.trace import SpanTracer
    from repro.serve import ContinuousEngine

    mesh = make_dev_mesh(conf["mesh"]["data"], conf["mesh"]["model"],
                         devices=devices)
    t = time.perf_counter()
    params = jax.block_until_ready(W.serving_params(arch, conf, seed, mesh))
    t_weights = time.perf_counter() - t
    tracer = SpanTracer(capacity=1 << 20) if trace else None
    recorder = recorder_class()()
    engine = ContinuousEngine(arch.model_config(conf, conf["engine"]),
                              params, engine_config(conf, mix), mesh=mesh,
                              ep_ranks=conf["ep_ranks"], tracer=tracer,
                              metrics=recorder)
    engine.warmup()
    print(f"set-up: weights {t_weights:.2f} s, engine and warmup "
          f"{time.perf_counter() - t - t_weights:.2f} s", file=sys.stderr)
    return engine, recorder, tracer


def open_devices(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices, after enabling the compile cache;
    None (with the reason on stderr) without a TPU or enough chips."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        print(f"needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"cell needs {chips} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return None
    return devices[:chips]


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, spec: Optional[dict] = None,
        mix: Optional[dict] = None, engine_hook=None,
        control: bool = False) -> dict:
    """One run of a cell; returns the result object (``None`` where the
    device check fails). ``mix`` replaces the cell's traffic file, and
    ``engine_hook`` is called with the warm engine (``faults.py`` breaks
    the timed path through it); with ``control`` the fp8 control is judged
    in the program's place."""
    spec = spec or load_spec()
    cell, conf, mix0, arch = resolve(spec, workload)
    mix = mix or mix0
    chips = cell["chips"]

    devices = open_devices(chips, require_tpu)
    if devices is None:
        return None
    t_devices = time.perf_counter() - T_START
    import jax

    D = arch.dims(conf)
    engine, recorder, tracer = build(arch, conf, mix, seed, devices, trace)
    if engine_hook is not None:
        engine_hook(engine)
    drv = ServeLoop(engine, T.Traffic(mix, D["V"], seed), mix, recorder)
    t = time.perf_counter()
    drv.fill()
    print(f"set-up: devices at {t_devices:.2f} s, fill "
          f"{time.perf_counter() - t:.2f} s", file=sys.stderr)
    compiles0 = engine.compile_counts()
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - T_START
    end = drv.window(seconds, trace)
    if trace:
        jax.profiler.stop_trace()
    reqs, steps, failed, t_win = drv.reqs, drv.steps, drv.failed, drv.t0
    new_compiles = sum(engine.compile_counts().values()) \
        - sum(compiles0.values())
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    extra = {"compiles_in_window": (new_compiles, 0, new_compiles == 0),
             "dropped_tokens": (engine.metrics.resched["dropped_tokens"], 0,
                                engine.metrics.resched["dropped_tokens"] == 0)}
    spans = []
    if tracer is not None:
        spans = [(ts, dur, name) for ph, name, _, ts, dur, _, _
                 in tracer.events() if ph == "X" and ts >= t_win]
    del engine, recorder, tracer, drv
    gc.collect()

    picked = sample(reqs, seed)
    halves = slots_per_half(picked, mix["slots"]["max_slots"])
    lim = conf["limits"]["checked_slots_per_half_min"]
    extra["checked_slots_per_half"] = (halves, lim, halves >= lim)
    served = {"steps": len(steps),
              "requests_due": sum(0 <= r.due <= end for r in reqs),
              "requests_finished": sum(r.finished for r in reqs),
              "requests_waiting": sum(not r.times for r in reqs),
              "tokens": sum(0 <= t <= end for r in reqs for t in r.times),
              "checked_requests": len(picked),
              "checked_slots": len({p.req.slot for p in picked})}
    print(f"window {end:.3f} s: {served}; setup {setup_s:.2f} s; "
          f"peak {peak / 1e9:.3f} GB", file=sys.stderr)
    t_ref = time.perf_counter()
    checks, readings = check(arch, conf, seed, picked, extra,
                             control=control)
    correct = all(ok for _, _, ok in checks.values())
    print(f"reference {time.perf_counter() - t_ref:.2f} s", file=sys.stderr)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(reqs), "failed": failed}
    if not trace:
        metrics = end_to_end(reqs, end, chips)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result["metrics"] = {m["name"]: metrics[m["name"]]
                             for m in spec["end_to_end"]
                             if workload in m.get("workloads", [workload])}
    else:
        result.update(per_layer(spec, workload, arch, conf, mix, chips,
                                steps, reqs, spans, log_dir, t_win, device))
        shutil.rmtree(log_dir, ignore_errors=True)
    result["device"] = device
    result["served"] = served
    result["readings"] = readings
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, (v, lim, _) in checks.items()}
    return result


def per_layer(spec, workload, arch, conf, mix, chips, steps, reqs, spans,
              log_dir, t_win, device):
    """Per-layer metrics and the breakdown of a traced run."""
    import trace_reduce as TR
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if device["kind"] not in peaks:
        raise SystemExit(f"no peaks for device kind {device['kind']!r}")
    red = None
    path = TR.find_xplane(log_dir)
    if path is not None:
        tr = TR.load(path)
        if len(tr.steps) and tr.chips:
            red = TR.reduce(tr)
    # the engine's spans on the trace's clock: the offset that puts the
    # harness's step starts on its bench.step annotations
    aligned = []
    if red is not None and spans:
        t0s = np.asarray([int(t_win + s.t0 * 1e9) for s in steps], np.int64)
        n = min(len(t0s), len(red.steps))
        off = int(np.median(red.steps[:n, 0] - t0s[:n]))
        aligned = [(ts + off, dur, name) for ts, dur, name in spans]
    ctx = Context(conf=conf, arch=arch, D=arch.dims(conf), mix=mix,
                  chips=chips, peak=peaks[device["kind"]], steps=steps,
                  requests=reqs,
                  spans=aligned, trace=red,
                  block_size=conf["engine"]["block_size"])
    metrics = {}
    for m in per_layer_metrics(spec, workload):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"metrics": metrics}
    if red is not None:
        window = red.steps[-1, 1] - red.steps[0, 0]
        device["busy_s"] = red.busy_window_ns * 1e-9
        device["window_s"] = window * 1e-9
        gaps = TR.idle_by_span(red, [(s, s + d, n) for s, d, n in aligned])
        out["breakdown"] = {"device_ops": TR.top(TR.labelled(red.op_ns)),
                            "idle_gaps": TR.top(gaps)}
    return out


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result, allow_nan=False))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return 1
    report(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
