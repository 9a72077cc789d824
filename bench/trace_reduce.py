"""From a profiler trace (``.xplane.pb``) to device busy time, program,
op, kernel and collective times, and idle gaps by host span.

Only ``jax.profiler.ProfileData`` is used to read the file. Device planes
are ``/device:TPU:<n>``; an op is an event on a plane's ``XLA Ops`` line,
a program execution one on its ``XLA Modules`` line. The harness marks
each engine step with a ``TraceAnnotation`` named ``bench.step`` on the
host; everything is measured inside those steps, and device work is
attributed to the step whose host interval holds the op's start.

    python bench/trace_reduce.py <file.xplane.pb>   # print a summary
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP = "bench.step"
ENQUEUE = "DoEnqueueProgram"
# control-flow ops whose events span the ops of their bodies
CONTAINER = re.compile(r"^%(while|conditional|call)[.\s]")
COLLECTIVE = re.compile(
    r"all-to-all|all-reduce|collective-permute|all-gather|reduce-scatter")


def find_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def module_name(name: str) -> str:
    """``jit_decode_step(12)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_label(name: str, width: int = 160) -> str:
    """An op event's HLO text cut to its name, result type and kind:
    layouts and operands dropped."""
    s = re.sub(r"\{[^{}]*\}", "", name)
    if " = (" not in s:
        s = s.split("(", 1)[0]
    return s[:width].rstrip()


def labelled(op_ns: Dict[str, float]) -> Dict[str, float]:
    """Op times summed by ``op_label``."""
    out: Dict[str, float] = {}
    for k, v in op_ns.items():
        out[op_label(k)] = out.get(op_label(k), 0.0) + v
    return out


def merged(iv: np.ndarray) -> np.ndarray:
    """Union of (start, end) intervals as disjoint intervals sorted by
    start."""
    if len(iv) == 0:
        return np.zeros((0, 2), np.int64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.r_[True, iv[1:, 0] > ends[:-1]]
    first = np.flatnonzero(new)
    return np.stack([iv[first, 0], np.maximum.reduceat(iv[:, 1], first)], 1)


def clip(iv: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Intervals cut to [lo, hi); empty ones dropped."""
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


@dataclass
class Chip:
    """One device's events: ops (start, end, name) and modules, on the
    host's clock (``offset_ns`` added to the device's own)."""
    ops: List[Tuple[int, int, str]] = field(default_factory=list)
    modules: List[Tuple[int, int, str]] = field(default_factory=list)
    offset_ns: int = 0


@dataclass
class Trace:
    chips: List[Chip]
    host: List[Tuple[int, int, str]]          # host annotations
    steps: np.ndarray                         # (n, 2) bench.step intervals


def _stat(e, key):
    for k, v in e.stats:
        if k == key:
            return v
    return None


def load(path: str) -> Trace:
    """Device events of every chip, shifted onto the host's clock, and the
    host's ``bench.*`` annotations.

    A device plane's clock is offset from the host's (by about 1.5 ms on
    a v5e). A program cannot start on a chip before the host has enqueued
    it: the host's ``DoEnqueueProgram`` event and the chip's module event
    share a ``run_id``. The offset taken is the least that puts every
    module after its enqueue, so each device event lies no earlier on the
    host's clock than it can have run."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    chips: Dict[int, Chip] = {}
    starts: Dict[int, Dict[int, int]] = {}       # chip -> run_id -> start
    host = []
    enqueued: Dict[int, int] = {}                # run_id -> first enqueue end
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = chips.setdefault(int(m.group(1)), Chip())
            runs = starts.setdefault(int(m.group(1)), {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip.ops.extend((int(e.start_ns), int(e.end_ns), e.name)
                                    for e in line.events)
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        chip.modules.append((int(e.start_ns), int(e.end_ns),
                                             module_name(e.name)))
                        rid = _stat(e, "run_id")
                        if rid is not None:
                            runs[int(rid)] = int(e.start_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((int(e.start_ns), int(e.end_ns), e.name))
                    elif e.name == ENQUEUE:
                        rid = _stat(e, "run_id")
                        if rid is not None:
                            rid = int(rid)
                            enqueued[rid] = min(enqueued.get(rid, 1 << 62),
                                                int(e.end_ns))
    for k, chip in chips.items():
        lags = [enqueued[r] - s for r, s in starts[k].items()
                if r in enqueued]
        off = max(lags) if lags else 0
        chip.offset_ns = off
        chip.ops = [(s + off, e + off, n) for s, e, n in chip.ops]
        chip.modules = [(s + off, e + off, n) for s, e, n in chip.modules]
    steps = np.asarray(sorted((s, e) for s, e, n in host if n == STEP),
                       np.int64).reshape(-1, 2)
    return Trace([chips[k] for k in sorted(chips)], host, steps)


def _step_of(starts: np.ndarray, steps: np.ndarray) -> np.ndarray:
    """Index of the step holding each start, -1 outside every step."""
    i = np.searchsorted(steps[:, 0], starts, side="right") - 1
    ok = (i >= 0) & (starts < steps[np.maximum(i, 0), 1])
    return np.where(ok, i, -1)


@dataclass
class Reduced:
    """Per-chip sums are averaged over chips."""
    chips: int
    steps: np.ndarray                          # (n, 2) host ns
    step_ns: int                               # time inside steps
    busy_ns: float                             # device busy inside steps
    busy_window_ns: float                      # busy, first to last step
    module_ns: Dict[str, float]                # per program, inside steps
    module_calls: Dict[str, float]             # executions per chip
    op_ns: Dict[str, float]                    # per op name
    collective_ns: float
    step_module_ns: List[Dict[str, float]]     # per step, per program
    idle_gaps: List[Tuple[int, int]]           # chip 0, inside steps

    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.step_ns

    def ops_matching(self, pattern: str) -> float:
        r = re.compile(pattern)
        return sum(v for k, v in self.op_ns.items() if r.search(k))

    def modules_matching(self, pattern: str) -> Tuple[float, float]:
        """(device ns, executions) of programs whose name matches."""
        r = re.compile(pattern)
        return (sum(v for k, v in self.module_ns.items() if r.search(k)),
                sum(v for k, v in self.module_calls.items() if r.search(k)))


def reduce(tr: Trace) -> Reduced:
    steps = tr.steps
    n_chips = max(len(tr.chips), 1)
    step_ns = int((steps[:, 1] - steps[:, 0]).sum())
    busy = busy_window = 0.0
    module_ns: Dict[str, float] = {}
    module_calls: Dict[str, float] = {}
    op_ns: Dict[str, float] = {}
    coll = 0.0
    per_step = [dict() for _ in range(len(steps))]
    gaps: List[Tuple[int, int]] = []
    for ci, chip in enumerate(tr.chips):
        ops = np.asarray([(s, e) for s, e, _ in chip.ops],
                         np.int64).reshape(-1, 2)
        k = _step_of(ops[:, 0], steps)
        inside = ops[k >= 0]
        # an op counts inside the step that launched it, up to its end
        inside[:, 1] = np.minimum(inside[:, 1], steps[k[k >= 0], 1])
        b = merged(inside)
        busy += float((b[:, 1] - b[:, 0]).sum())
        w = merged(clip(ops, int(steps[0, 0]), int(steps[-1, 1])))
        busy_window += float((w[:, 1] - w[:, 0]).sum())
        if ci == 0:
            bk = _step_of(b[:, 0], steps)
            for j, (s, e) in enumerate(steps):
                mine = b[bk == j].reshape(-1)
                edges = np.concatenate([[s], mine, [e]]).reshape(-1, 2)
                gaps.extend((int(gs), int(ge)) for gs, ge in edges
                            if ge > gs)
        for (s, e, n), kk in zip(chip.ops, k):
            if kk < 0 or CONTAINER.match(n):
                continue
            op_ns[n] = op_ns.get(n, 0.0) + (e - s) / n_chips
            if COLLECTIVE.search(n):
                coll += (e - s) / n_chips
        mods = np.asarray([(s, e) for s, e, _ in chip.modules],
                          np.int64).reshape(-1, 2)
        mk = _step_of(mods[:, 0], steps)
        for (s, e, n), kk in zip(chip.modules, mk):
            if kk < 0:
                continue
            module_ns[n] = module_ns.get(n, 0.0) + (e - s) / n_chips
            module_calls[n] = module_calls.get(n, 0.0) + 1.0 / n_chips
            per_step[kk][n] = per_step[kk].get(n, 0.0) + (e - s) / n_chips
    return Reduced(n_chips, steps, step_ns, busy / n_chips,
                   busy_window / n_chips, module_ns,
                   module_calls, op_ns, coll, per_step, gaps)


def idle_by_span(red: Reduced, spans: List[Tuple[int, int, str]]
                 ) -> Dict[str, float]:
    """Idle nanoseconds of chip 0 by the innermost host span holding each
    gap's midpoint (``spans`` on the trace clock; ``harness`` where no
    engine span holds it)."""
    spans = sorted(spans, key=lambda x: x[0])
    starts = np.asarray([s for s, _, _ in spans], np.int64)
    out: Dict[str, float] = {}
    for gs, ge in red.idle_gaps:
        mid = (gs + ge) // 2
        name = "harness"
        i = int(np.searchsorted(starts, mid, side="right")) - 1
        # the latest-starting span that holds mid is the innermost
        for j in range(i, max(i - 64, -1), -1):
            s, e, n = spans[j]
            if s <= mid < e:
                name = n
                break
        out[name] = out.get(name, 0.0) + (ge - gs)
    return out


def top(d: Dict[str, float], n: int = 10, scale: float = 1e-9):
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def main(argv=None):
    path = (argv or sys.argv[1:])[0]
    red = reduce(load(path))
    print(json.dumps({
        "chips": red.chips, "steps": len(red.steps),
        "step_s": red.step_ns * 1e-9, "busy_s": red.busy_ns * 1e-9,
        "idle_share": red.idle_share() if red.step_ns else None,
        "collective_s": red.collective_ns * 1e-9,
        "modules": top(red.module_ns, 20),
        "ops": top(labelled(red.op_ns), 20)},
        indent=1))


if __name__ == "__main__":
    main()
