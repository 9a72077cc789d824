"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (EXPERIMENTS.md Sec
Roofline):

  compute    = HLO_FLOPs_per_device  / peak_FLOPs
  memory     = HLO_bytes_per_device  / HBM_bw
  collective = collective_bytes_per_device / ICI_bw

``compiled.cost_analysis()`` reports the per-device (SPMD-partitioned)
program, so all terms are per-device already — equivalent to the global
form divided by chips. collective_bytes comes from parsing the optimized
HLO: we sum the RESULT-shape bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (async -start variants
counted once, -done skipped).

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~45 GB/s
effective per ICI link x 2 links per torus axis (configurable below).
"""

from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict

# --------------------------------------------------------------------------
# hardware constants (TPU v5e)
# --------------------------------------------------------------------------

PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_LINK_BW = 45e9           # effective bytes/s per link
ICI_LINKS = 2                # usable links per torus axis for a collective
ICI_BW = ICI_LINKS * ICI_LINK_BW

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over all shapes in an HLO result type (handles tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_COMP_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_CALL_REF = re.compile(r"(?:body|condition|to_apply|calls|"
                       r"branch_computations=\{[^}]*)=?%?([\w.\-]+)")


def _computation_bodies(hlo_text: str) -> Dict[str, str]:
    """Split HLO module text into {computation_name: body_text}."""
    comps: Dict[str, str] = {}
    name, buf = None, []
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = _COMP_HEADER.match(stripped)
        if m and stripped.endswith("{"):
            name = m.group(1)
            buf = []
            continue
        if name is not None:
            if stripped == "}":
                comps[name] = "\n".join(buf)
                name = None
            else:
                buf.append(line)
    return comps


def _loop_body_computations(comps: Dict[str, str]) -> set:
    """Names of computations reachable from any while-op body."""
    # direct while bodies
    roots = set()
    calls: Dict[str, set] = {n: set() for n in comps}
    for cname, body in comps.items():
        for line in body.splitlines():
            if " while(" in line or "=while(" in line:
                m = re.search(r"body=%?([\w.\-]+)", line)
                if m:
                    roots.add(m.group(1))
            for ref in re.findall(r"(?:to_apply|calls|body|condition)=%?"
                                  r"([\w.\-]+)", line):
                calls[cname].add(ref)
    # transitive closure from roots
    seen = set()
    stack = list(roots)
    while stack:
        n = stack.pop()
        if n in seen:
            continue
        seen.add(n)
        stack.extend(calls.get(n, ()))
    return seen


def collective_bytes(hlo_text: str, loop_trips: int = 1) -> Dict[str, int]:
    """Per-collective-kind result bytes from optimized HLO (per device).

    XLA's static analyses (and a flat text scan) count while-loop bodies
    ONCE; collectives inside a loop body (the layer scan) are multiplied
    by ``loop_trips`` (= num_layers for our models — the layer scan is
    the only loop containing collectives)."""
    comps = _computation_bodies(hlo_text)
    in_loop = _loop_body_computations(comps)

    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0

    def scan_text(text: str, mult: int):
        for line in text.splitlines():
            if "=" not in line:
                continue
            _, _, rest = line.partition("=")
            rest = rest.strip()
            m = re.match(r"^((?:\([^)]*\))|(?:[\w\[\],{}: /#*]+?))\s+"
                         r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute)(-start)?\(", rest)
            if not m:
                continue
            type_str, kind = m.group(1), m.group(2)
            out[kind] += _shape_bytes(type_str) * mult
            out["count"] += 1

    if comps:
        for cname, body in comps.items():
            scan_text(body, loop_trips if cname in in_loop else 1)
    else:                      # fallback: flat scan, no loop correction
        scan_text(hlo_text, 1)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


# --------------------------------------------------------------------------
# analytic op model (primary source for compute/memory terms)
# --------------------------------------------------------------------------
#
# XLA's cost_analysis counts while-loop bodies ONCE, so the raw HLO flops /
# bytes undercount scanned layers (and seq scans) by up to the trip count.
# The roofline table therefore uses this analytic model for the compute and
# memory terms — validated against HLO on unrolled (hybrid) configs — and
# keeps the raw HLO numbers alongside for reference.

def analytic_flops(cfg, shape) -> float:
    """Per-STEP total (all devices) FLOPs for the step a shape lowers."""
    from repro.core.simulator import (attention_flops, dense_ffn_flops_per_token,
                                      ffn_flops_per_token)
    L, d, V = cfg.num_layers, cfg.d_model, cfg.vocab_size

    if shape.kind == "decode":
        tokens = shape.global_batch
        ctx = shape.seq_len
        w = cfg.sliding_window or (4096 if shape.name == "long_500k" else 0)
        s_eff = min(ctx, w) if w else ctx
        if cfg.family == "ssm":
            per_tok_layer = 14 * d * d            # rwkv6 time+channel mix
            attn = per_tok_layer * tokens * L
        elif cfg.family == "hybrid":
            dr = cfg.rnn_width or d
            rec_l = (4 * d * dr + 3 * dr) * 2 * tokens   # gates + out proj
            loc_l = attention_flops(cfg, tokens, min(ctx, cfg.local_window))
            n_rec = sum(1 for i in range(L)
                        if cfg.block_pattern[i % len(cfg.block_pattern)]
                        == "recurrent") if cfg.block_pattern else 0
            attn = rec_l * n_rec + loc_l * (L - n_rec)
        else:
            attn = attention_flops(cfg, tokens, s_eff, causal=False) * L
        ffn = (ffn_flops_per_token(cfg)
               + dense_ffn_flops_per_token(cfg)) * tokens * L
        head = 2 * tokens * d * V
        return attn + ffn + head

    tokens = shape.global_batch * shape.seq_len
    if cfg.input_mode == "mixed" and cfg.num_prefix_embeddings:
        tokens = shape.global_batch * (shape.seq_len + cfg.num_prefix_embeddings)
    if cfg.family == "ssm":
        attn = 14 * d * d * tokens * L
    elif cfg.family == "hybrid":
        dr = cfg.rnn_width or d
        rec_l = (4 * d * dr + 3 * dr) * 2 * tokens
        loc_l = attention_flops(cfg, tokens, min(shape.seq_len, cfg.local_window))
        n_rec = sum(1 for i in range(L)
                    if cfg.block_pattern[i % len(cfg.block_pattern)] == "recurrent")
        attn = rec_l * n_rec + loc_l * (L - n_rec)
    else:
        attn = attention_flops(cfg, tokens, shape.seq_len) * L
    ffn = (ffn_flops_per_token(cfg) + dense_ffn_flops_per_token(cfg)) * tokens * L
    head = 2 * tokens * d * V
    enc = 0.0
    if cfg.is_encdec:
        e = cfg.encoder
        etoks = shape.global_batch * e.max_source_len
        enc = (attention_flops(cfg, etoks, e.max_source_len)
               + 2 * 3 * e.d_model * e.d_ff * etoks) * e.num_layers
    fwd = attn + ffn + head + enc
    return 3.0 * fwd if shape.kind == "train" else fwd


def analytic_hbm_bytes(cfg, shape, chips: int, *, act_coeff: float = 10.0
                       ) -> float:
    """Per-DEVICE HBM traffic per step (weights + activations + cache/opt).

    Coefficients are deliberately simple and documented:
      * weights: each device reads its resident shard once per step
        (train: + grad write + fp32 Adam moments read+write).
      * duplication: with ``duplication_slots > 0`` the persistent replica
        store (a second copy of the home experts plus the replica slots,
        ``repro.runtime.ReplicaStore``) adds one read of the extra slot
        entries per MoE layer per step — the memory-side price of serving
        without a per-step weight collective. ``MoEConfig.
        store_hbm_budget_gb`` caps the slots this term may grow to
        (``core.placement.clamp_dup_slots``).
      * activations: ~act_coeff residency round-trips per layer
        (norms, attention in/out, FFN in/out, residuals).
      * decode: full KV-cache shard read per step (the decode bottleneck).
    """
    B = 2  # bf16
    params = cfg.num_params()
    w = params * B / chips
    if (cfg.moe is not None and cfg.moe.duplication_slots > 0
            and shape.kind != "train"):
        e = cfg.moe
        ff_mult = 3 if cfg.activation == "swiglu" else 2
        expert_bytes = ff_mult * cfg.d_model * e.d_ff_expert * B
        w += e.duplication_slots * expert_bytes * cfg.num_layers
    if shape.kind == "train":
        # fwd read + bwd read + grad write (bf16) + moments r/w (fp32 x2 x2)
        w = params * (4 * 3 + 2 * 2 + 4 * 4) / chips / 2  # fp32 params
    tokens_local = shape.global_batch * shape.seq_len / chips
    if shape.kind == "decode":
        tokens_local = max(shape.global_batch / chips, 1.0 / chips)
    act = act_coeff * tokens_local * cfg.d_model * B * cfg.num_layers
    if shape.kind == "train":
        act *= 2.0        # bwd re-reads activations
    cache = 0.0
    if shape.kind == "decode":
        w_win = cfg.sliding_window or (4096 if shape.name == "long_500k" else 0)
        clen = min(shape.seq_len, w_win) if w_win else shape.seq_len
        if cfg.family == "ssm":
            state = cfg.num_heads * cfg.head_dim * cfg.head_dim * 4
            cache = shape.global_batch * state * cfg.num_layers / chips
        elif cfg.family == "hybrid":
            dr = cfg.rnn_width or cfg.d_model
            cache = shape.global_batch * (dr * 4 + cfg.local_window
                                          * cfg.num_kv_heads * cfg.head_dim
                                          * B) * cfg.num_layers / chips
        elif cfg.attention == "mla" and cfg.mla is not None:
            cache = (shape.global_batch * clen
                     * (cfg.mla.kv_lora_rank + cfg.mla.rope_head_dim) * B
                     * cfg.num_layers / chips)
        else:
            cache = (shape.global_batch * clen * 2 * cfg.num_kv_heads
                     * cfg.head_dim * B * cfg.num_layers / chips)
        cache = max(cache, 0.0)
    return w + act + cache


# --------------------------------------------------------------------------
# model flops (the "useful compute" yardstick)
# --------------------------------------------------------------------------

def model_flops(cfg, shape) -> float:
    """6·N_active·D for training, 2·N_active·D for inference (per step).

    N_active counts only activated experts for MoE (paper/industry
    convention); D = tokens processed by the step (decode: one per seq).
    """
    n = cfg.active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: 1 new token/seq


# --------------------------------------------------------------------------
# report
# --------------------------------------------------------------------------

@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # analytic op model (primary: compute/memory terms)
    analytic_flops_per_device: float
    analytic_hbm_per_device: float
    # raw HLO static analysis (reference; while bodies counted once)
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    # loop-corrected collective bytes from compiled HLO (primary)
    collective_bytes_per_device: float
    collective_breakdown: Dict[str, int] = field(default_factory=dict)
    model_flops_total: float = 0.0
    # memory analysis (bytes, per device)
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    peak_bytes: int = 0

    @property
    def compute_s(self) -> float:
        return self.analytic_flops_per_device / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.analytic_hbm_per_device / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def total_s(self) -> float:
        """Roofline step time = max of the three overlappable terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / analytic FLOPs: how much of executed compute is
        'useful' 6ND/2ND work (catches attention-quadratic, vocab-head,
        remat and capacity-padding overheads)."""
        total = self.analytic_flops_per_device * self.chips
        return self.model_flops_total / total if total else 0.0

    def row(self) -> Dict:
        d = asdict(self)
        d.update(compute_s=self.compute_s, memory_s=self.memory_s,
                 collective_s=self.collective_s, dominant=self.dominant,
                 total_s=self.total_s,
                 useful_flops_ratio=self.useful_flops_ratio)
        return d


def analyze(arch: str, shape, mesh_name: str, chips: int, compiled,
            cfg=None) -> RooflineReport:
    """Build a report from a jax ``compiled`` object."""
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    trips = cfg.num_layers if cfg is not None else 1
    coll = collective_bytes(hlo, loop_trips=trips)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = dict(
                argument_bytes=int(getattr(ma, "argument_size_in_bytes", 0)),
                output_bytes=int(getattr(ma, "output_size_in_bytes", 0)),
                temp_bytes=int(getattr(ma, "temp_size_in_bytes", 0)),
                peak_bytes=int(getattr(ma, "peak_memory_in_bytes", 0)
                               or getattr(ma, "temp_size_in_bytes", 0)),
            )
    except Exception:
        pass

    mf = model_flops(cfg, shape) if cfg is not None else 0.0
    af = analytic_flops(cfg, shape) / chips if cfg is not None else flops
    ab = analytic_hbm_bytes(cfg, shape, chips) if cfg is not None else hbm
    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        analytic_flops_per_device=af, analytic_hbm_per_device=ab,
        hlo_flops_per_device=flops, hlo_bytes_per_device=hbm,
        collective_bytes_per_device=coll["total"],
        collective_breakdown={k: v for k, v in coll.items()
                              if k in _COLLECTIVES or k == "count"},
        model_flops_total=mf, **mem)


def save_report(path: str, report: RooflineReport) -> None:
    import os
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(report.row(), f, indent=1)
