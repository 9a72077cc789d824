"""The engine's spans on the profiler's clock and the model's named
scopes, on the CPU at a tiny size: an engine step writes its span tree
into the JAX profiler's host plane under the ring buffer's names; a
disabled tracer records nothing and is handed no span arguments; the
compiled decode program files its expert GEMMs under ``moe.expert_ffn``
and the layer scan's own slicing and updating under ``layers``. Then the
benchmark's readers of these spans and scopes (``bench/``), each against
inputs worked out by hand."""

import dataclasses
import importlib.util
import os
import sys
import types

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.obs import SpanTracer
from repro.serve import ContinuousConfig, ContinuousEngine, ServeRequest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.append(BENCH)        # after everything else: shadows nothing

import hlo_scopes as HS  # noqa: E402
import trace_reduce as TR  # noqa: E402


def _reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def engine_factory():
    """A tiny Mixtral on a 1x1 mesh, so MoE runs the EP dispatch the
    one-chip deployment runs."""
    from repro.launch.mesh import make_dev_mesh
    from repro.models.transformer import init_params

    cfg = get_config("mixtral-8x7b").reduced()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=cfg.moe.num_experts / cfg.moe.top_k))
    mesh = make_dev_mesh(1, 1, devices=jax.devices()[:1])
    params = init_params(jax.random.PRNGKey(0), cfg, mesh=mesh)
    ccfg = ContinuousConfig(max_slots=4, prefill_len=32, block_size=8,
                            max_len=64, strategy="none", dup_slots=0)

    def make(tracer=None):
        eng = ContinuousEngine(cfg, params, ccfg, mesh=mesh, ep_ranks=1,
                               tracer=tracer)
        eng.warmup()
        return eng

    return cfg, make


def _serve(eng, cfg, n=3, steps=4):
    rng = np.random.default_rng(0)
    for i in range(n):
        eng.submit(ServeRequest(
            rid=i, tokens=rng.integers(0, cfg.vocab_size, 12).tolist(),
            max_new_tokens=6))
    for k in range(steps):
        eng.step(float(k))


def _host_events(log_dir):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(TR.find_xplane(log_dir))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((int(e.start_ns), int(e.end_ns), e.name,
                            {k: v for k, v in e.stats})
                           for e in line.events)
    return out


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_engine_spans_land_in_the_profilers_host_plane(engine_factory,
                                                       tmp_path):
    cfg, make = engine_factory
    tracer = SpanTracer()
    eng = make(tracer)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, cfg)
    finally:
        jax.profiler.stop_trace()
    host = _host_events(str(tmp_path))
    ring = {name for ph, name, *_ in tracer.events()}
    span_names = {"step", "plan", "admission", "prefill", "prefill.sync",
                  "decode", "decode.inputs", "decode.launch", "decode.sync",
                  "decode.tokens", "observe", "record"}
    assert span_names | {"request.submit"} <= ring
    assert ring <= {name for _, _, name, _ in host}

    def of(name):
        return [e for e in host if e[2] == name]

    for sync in of("decode.sync"):
        dec = [d for d in of("decode") if _inside(sync, d)]
        assert len(dec) == 1
        assert any(_inside(dec[0], s) for s in of("step"))
    assert len(of("decode.sync")) == 4
    for sync in of("prefill.sync"):
        assert any(_inside(sync, p) for p in of("prefill"))
    assert sorted(e[3]["rid"] for e in of("request.submit")) == [0, 1, 2]
    assert sorted(e[3]["rid"] for e in of("prefill")) == [0, 1, 2]


class _SpyTracer(SpanTracer):
    """A disabled tracer that keeps every ``args`` it is handed."""

    def __init__(self):
        super().__init__(enabled=False)
        self.handed = []

    def span(self, name, cat="serve", track=None, args=None):
        self.handed.append(args)
        return super().span(name, cat, track, args)

    def instant(self, name, cat="serve", track=None, args=None):
        self.handed.append(args)
        return super().instant(name, cat, track, args)


def test_disabled_tracer_records_nothing_and_gets_no_args(engine_factory):
    cfg, make = engine_factory
    spy = _SpyTracer()
    eng = make(spy)
    _serve(eng, cfg)
    assert spy.events() == []
    assert len(spy.handed) >= 4 * 10          # every span site was reached
    assert all(a is None for a in spy.handed)


def test_decode_program_scopes(engine_factory):
    cfg, make = engine_factory
    texts = make().program_texts()
    assert set(texts) == {"prefill_step", "decode_step",
                          "write_prefill_blocks"}
    assert HS.module_of(texts["decode_step"]) == "jit_decode_step"
    dec = texts["decode_step"]
    m = HS.scope_map(dec)
    gemms, scan_ops = [], []
    for name, path in HS.op_names(dec):
        if path.endswith(("std,sdf->stf/dot_general",
                          "stf,sfd->std/dot_general")):
            gemms.append(name)
        if path.endswith(("layers/while/body/dynamic_slice",
                          "layers/while/body/dynamic_update_slice")):
            scan_ops.append(name)
    assert len(gemms) >= 3 and {m[g] for g in gemms} == {"moe.expert_ffn"}
    assert scan_ops and {m[g] for g in scan_ops} == {"layers"}
    assert {"attn.qkv", "attn.kv_write", "attn.paged", "attn.out",
            "moe.route", "moe.pack", "moe.combine", "lm_head",
            "layer.body"} <= set(m.values())
    assert set(HS.scope_map(texts["write_prefill_blocks"]).values()) \
        == {"attn.kv_write"}


def test_scope_of_takes_the_innermost_named_scope():
    assert HS.scope_of("jit(f)/layers/while/body/closed_call/layer.body/"
                       "moe.expert_ffn/dot_general") == "moe.expert_ffn"
    assert HS.scope_of("jit(f)/layers/while/body/dynamic_update_slice") \
        == "layers"
    assert HS.scope_of("jit(f)/embed/gather") is None


def test_scope_ns_by_hand():
    text = "\n".join([
        "HloModule jit_decode_step, is_scheduled=true",
        "ENTRY %main {",
        '  %fusion.1 = bf16[8] fusion(%p), metadata={op_name="jit(decode_'
        'step)/layers/while/body/closed_call/layer.body/moe.expert_ffn/'
        'dot_general" stack_frame_id=1}',
        '  %fusion.2 = bf16[8] fusion(%p), metadata={op_name="jit(decode_'
        'step)/layers/while/body/dynamic_update_slice"}',
        "  %copy.3 = bf16[8] copy(%p)",
        "}"])
    maps = {HS.module_of(text): HS.scope_map(text)}
    assert maps == {"jit_decode_step": {"%fusion.1": "moe.expert_ffn",
                                        "%fusion.2": "layers"}}
    chip = TR.Chip(
        ops=[(10, 40, "%fusion.1 = bf16[8] fusion(...)"),
             (40, 50, "%fusion.2 = bf16[8] fusion(...)"),
             (50, 55, "%copy.3 = bf16[8] copy(...)"),
             (60, 70, "%while.4 = (s32[]) while(...)"),   # container
             (110, 120, "%fusion.1 = bf16[8] fusion(...)"),  # other program
             (150, 160, "%fusion.1 = bf16[8] fusion(...)"),  # outside steps
             (210, 240, "%fusion.1 = bf16[8] fusion(...)")],
        modules=[(10, 80, "jit_decode_step"), (100, 130, "jit_prefill_step"),
                 (150, 170, "jit_decode_step"),
                 (205, 250, "jit_decode_step")])
    steps = np.asarray([[0, 140], [200, 300]], np.int64)
    tr = TR.Trace([chip], [], steps)
    got = HS.scope_ns(tr, maps)
    assert got == {("jit_decode_step", "moe.expert_ffn"): 30.0 + 30.0,
                   ("jit_decode_step", "layers"): 10.0,
                   ("jit_decode_step", None): 5.0}
    (k0, v0), (k1, v1) = HS.top(got, 2)
    assert (k0, k1) == ("jit_decode_step/moe.expert_ffn",
                        "jit_decode_step/layers")
    assert (v0, v1) == pytest.approx((60e-9, 10e-9))


def _ctx(spans, steps=(), requests=()):
    return types.SimpleNamespace(spans=spans, steps=list(steps),
                                 requests=list(requests))


def test_host_ms_per_step_by_hand():
    read = _reader("host_ms_per_step")
    ms = 1_000_000
    spans = [(0, 40 * ms, "step"), (1 * ms, 30 * ms, "decode"),
             (2 * ms, 25 * ms, "decode.sync"),
             (50 * ms, 80 * ms, "step"), (51 * ms, 36 * ms, "prefill"),
             (52 * ms, 35 * ms, "prefill.sync"),
             (90 * ms, 30 * ms, "decode.sync")]
    # step 1: 40 - 25; step 2: 80 - 35 - 30
    assert read(_ctx(spans)) == pytest.approx((15 + 15) / 2)
    # a program without the sync spans reads nothing
    assert read(_ctx([s for s in spans if "sync" not in s[2]])) is None


def test_ttft_wait_ms_by_hand():
    read = _reader("ttft_wait_ms")
    ms = 1_000_000

    def req(i, submit, first):
        return types.SimpleNamespace(index=i, submit=submit, times=[first])

    steps = [types.SimpleNamespace(t0=0.000, t1=0.040),
             types.SimpleNamespace(t0=0.041, t1=0.121),
             types.SimpleNamespace(t0=0.122, t1=0.160)]
    spans = [(0, 40 * ms, "step"),
             (41 * ms, 80 * ms, "step"), (42 * ms, 38 * ms, "prefill"),
             (80 * ms, 39 * ms, "prefill"),
             (122 * ms, 38 * ms, "step")]
    reqs = [req(7, 0.020, 0.121), req(5, 0.010, 0.121),
            req(1, -5.0, -4.9)]                      # before the window
    # in submission order: rid 5 took the first prefill, rid 7 the second
    waits = [121 - 10 - 38, 121 - 20 - 39]
    assert read(_ctx(spans, steps, reqs)) == pytest.approx(np.median(waits))
    # a step whose prefill spans do not match its requests is left out
    assert read(_ctx(spans[:3] + spans[4:], steps, reqs)) is None
