"""Device time by the model's named scopes.

The model marks the parts of its step programs with ``jax.named_scope``
(``repro.models``, ``repro.moe.dispatch``, ``repro.serve.kvcache``). XLA
keeps each instruction's scope path in its ``metadata={op_name=...}``,
e.g. ``jit(decode_step)/layers/while/body/closed_call/layer.body/
moe.expert_ffn/dot_general``. The profiler's device events name the
instruction (``%fusion.224 = ...``) but carry no metadata, so the scope of
an event is looked up in the optimized HLO text of the program that ran
it (``ContinuousEngine.program_texts``, taken on the chip: fusion names
are the TPU compiler's).

An instruction's scope is the innermost of ``SCOPES`` on its path; JAX's
own path parts (``while``, ``body``, ``closed_call``, ``shard_map``, the
primitive) are skipped. The layer scan runs under ``layers`` and its body
under ``layer.body``, so an instruction whose innermost scope is
``layers`` is the scan's own slicing of its stacked inputs and writing of
its stacked outputs: for the decode program, a copy of the KV pool.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

import trace_reduce as TR

SCOPES = frozenset((
    "layers", "layer.body", "lm_head",
    "attn.qkv", "attn.kv_write", "attn.paged", "attn.causal", "attn.out",
    "moe.route", "moe.pack", "moe.expert_ffn", "moe.combine"))

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+)\s*=(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def scope_of(op_name: str) -> Optional[str]:
    """Innermost named scope on an ``op_name`` path, None outside all."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def module_of(hlo_text: str) -> str:
    """The program's module name (``jit_decode_step``), as the profiler's
    ``XLA Modules`` line names it."""
    m = _MODULE.match(hlo_text)
    if m is None:
        raise ValueError("not an HLO module text")
    return m.group(1)


def op_names(hlo_text: str) -> Iterator[Tuple[str, str]]:
    """(instruction name, ``op_name``) of every instruction that has one."""
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        meta = m and _OP_NAME.search(m.group(2))
        if meta:
            yield m.group(1), meta.group(1)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name (``%fusion.224``) -> innermost named scope of its
    ``op_name``, for every instruction under one."""
    return {name: scope_of(path) for name, path in op_names(hlo_text)
            if scope_of(path) is not None}


def instruction(op_event_name: str) -> str:
    """``%fusion.224 = bf16[...] fusion(...)`` -> ``%fusion.224``."""
    return op_event_name.split(" ", 1)[0]


def scope_ns(tr: TR.Trace, maps: Dict[str, Dict[str, str]]
             ) -> Dict[Tuple[str, Optional[str]], float]:
    """Device ns by (program, scope), averaged over chips, for the ops
    ``trace_reduce.reduce`` counts (those starting inside a ``bench.step``,
    control-flow containers left out). Each op belongs to the program
    execution that holds its start, and takes the scope its instruction
    has in that program's map (``maps``: module name -> ``scope_map``);
    None where it has none. Programs without a map are left out."""
    steps = tr.steps
    out: Dict[Tuple[str, Optional[str]], float] = {}
    n_chips = max(len(tr.chips), 1)
    for chip in tr.chips:
        mods = sorted(chip.modules)
        m_iv = np.asarray([(s, e) for s, e, _ in mods],
                          np.int64).reshape(-1, 2)
        o_start = np.asarray([s for s, _, _ in chip.ops], np.int64)
        in_step = TR._step_of(o_start, steps) >= 0
        mi = np.searchsorted(m_iv[:, 0], o_start, side="right") - 1
        for (s, e, name), ok, i in zip(chip.ops, in_step, mi):
            if not ok or i < 0 or s >= m_iv[i, 1] or TR.CONTAINER.match(name):
                continue
            prog = mods[i][2]
            if prog not in maps:
                continue
            key = (prog, maps[prog].get(instruction(name)))
            out[key] = out.get(key, 0.0) + (e - s) / n_chips
    return out


def top(ns: Dict[Tuple[str, Optional[str]], float], n: int = 10
        ) -> List[list]:
    """Ten costliest (program, scope) pairs as ``["program/scope", s]``,
    the format of ``trace_reduce.top``; ``-`` for no scope."""
    return TR.top({f"{p}/{s or '-'}": v for (p, s), v in ns.items()}, n)
