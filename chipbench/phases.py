"""The training step's phases on the device and its host spans: forward,
backward, recompute, optimizer and the rest, read from the op_name metadata
the program's ``jax.named_scope``s leave on each compiled instruction, and
the idle time between steps put down to the program's ``repro.train.*``
spans.

The phase of an op_name is decided on its ``/``-separated components, in
this order:

1. ``rematted_computation`` (JAX's marker for a recomputed forward): recompute
2. a component starting ``transpose(``: backward
3. ``optimizer``, with any transform around it: optimizer
4. ``model``, likewise (``jvp(model)``): forward
5. anything else: other

The chip's trace names an op by its instruction's HLO text without the
metadata, so a device event finds its phase by instruction name in the
compiled program's text (:func:`op_phases`).
"""

from __future__ import annotations

import re
from typing import Dict, Sequence

import devtrace

PHASES = ("forward", "backward", "recompute", "optimizer", "other")
PROGRAM_SPAN = "repro.train."
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[A-Za-z_][\w.]*\((.*)\)$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=")


def _core(component: str) -> str:
    """``model`` for ``transpose(jvp(model))``: the name inside any
    transforms wrapped around it."""
    while m := _WRAPPED.match(component):
        component = m.group(1)
    return component


def phase_of(op_name: str) -> str:
    parts = op_name.split("/")
    if "rematted_computation" in parts:
        return "recompute"
    if any(p.startswith("transpose(") for p in parts):
        return "backward"
    cores = {_core(p) for p in parts}
    if "optimizer" in cores:
        return "optimizer"
    if "model" in cores:
        return "forward"
    return "other"


def op_phases(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> phase, for every instruction of the compiled
    program's HLO text that carries op_name metadata."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        meta = _OP_NAME.search(line) if m else None
        if meta:
            out[devtrace.op_name(m.group(1))] = phase_of(meta.group(1))
    return out


def phase_time(events: Sequence[devtrace.Event], phases: Dict[str, str],
               lo: float, hi: float) -> Dict[str, float]:
    """Device ns of each phase over the operations that start in
    ``[lo, hi)``; ops missing from ``phases`` count as other.  A loop or
    call whose event spans the operations inside it is left out as
    ``devtrace.top_ops`` leaves it out, so the phases add up to the total of
    the ops ``top_ops`` counts."""
    tot = dict.fromkeys(PHASES, 0.0)
    for e in events:
        name = devtrace.op_name(e[0])
        if lo <= e[1] < hi and not devtrace.CONTAINER.match(name):
            tot[phases.get(name, "other")] += e[2]
    return tot


def idle_split(events: Sequence[devtrace.Event], spans: Sequence[devtrace.Event],
               lo: float, hi: float, min_ns: float = 0.0) -> Dict[str, float]:
    """The device's idle stretches in ``[lo, hi)`` no shorter than
    ``min_ns``, their ns put down to the program span (``repro.train.*``,
    the step itself left out) the host was in, and the rest to
    ``"outside"``; ``"gaps"`` counts the stretches."""
    busy = devtrace.union(devtrace.clip(events, lo, hi))
    gaps, t = [], lo
    for a, b in busy + [(hi, hi)]:
        if a - t >= max(min_ns, 1e-9):
            gaps.append((t, a))
        t = max(t, b)
    inner = [s for s in spans if s[0].startswith(PROGRAM_SPAN) and s[0] != "repro.train.step"]
    out: Dict[str, float] = {"gaps": float(len(gaps)), "outside": 0.0}
    for a, b in gaps:
        covered = 0.0
        for name, s, d in inner:
            ov = min(b, s + d) - max(a, s)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                covered += ov
        out["outside"] += (b - a) - covered
    return out
