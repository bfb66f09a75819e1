"""From a profiler trace to numbers: device busy time, kernel time,
exposed collective time, the longest device operations and idle gaps.

The trace is first reduced to plain event records, ``(name, start_ns,
duration_ns)``: the device operations of each chip (the "XLA Ops"
line of each ``/device:TPU:n`` plane) and the host spans the benchmark
opened with ``jax.profiler.TraceAnnotation`` (names starting
``chipbench.``).  Everything after :func:`load` works on those records, so
the tests check it on a small recorded excerpt.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, dur_ns

SPAN_PREFIX = "chipbench."
#: operations whose trace event encloses the events of the operations they run
CONTAINER = re.compile(r"(while|conditional|call)([.]|$)")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def load(trace_dir: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """``({device plane: [op events]}, [host spans])`` of the newest trace
    under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def window(spans: Sequence[Event], name: str) -> Tuple[float, float]:
    """``(start_ns, end_ns)`` of the one host span called ``name``."""
    hits = [(s, s + d) for n, s, d in spans if n == name]
    if len(hits) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(hits)}")
    return hits[0]


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of ``events`` cut to ``[lo, hi)``, empty ones dropped."""
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def length(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in ``[lo, hi)`` in which at least one operation ran."""
    return length(union(clip(events, lo, hi)))


def collective_exposed_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in ``[lo, hi)`` in which a collective ran and nothing else did."""
    coll = union(clip((e for e in events if COLLECTIVE.search(e[0])), lo, hi))
    other = union(clip((e for e in events if not COLLECTIVE.search(e[0])), lo, hi))
    exposed, j = 0.0, 0
    for a, b in coll:
        covered = 0.0
        while j < len(other) and other[j][1] <= a:
            j += 1
        k = j
        while k < len(other) and other[k][0] < b:
            covered += min(b, other[k][1]) - max(a, other[k][0])
            k += 1
        exposed += (b - a) - covered
    return exposed


# ---------------------------------------------------------------- kernels

_SHAPE = re.compile(r"(bf16|f32|f16|s32)\[([0-9,]*)\]")


def _kind_of(hlo_line: str) -> Optional[str]:
    """"fwd", "dq" or "dkv" for the HLO line of a flash-attention kernel.

    Told apart by their results, which the kernels fix: the forward gives
    (out, lse) with lse ``(B, H, S, 1)`` in f32; the dq kernel gives one
    array; the dk/dv kernel gives two of the same shape."""
    if "tpu_custom_call" not in hlo_line:
        return None
    head = hlo_line.split("custom-call(")[0].split("=", 1)[-1]
    outs = [(t, tuple(int(x) for x in dims.split(",") if x))
            for t, dims in _SHAPE.findall(head)]
    outs = [o for o in outs if len(o[1]) == 4]
    if len(outs) == 2 and outs[1][0] == "f32" and outs[1][1][-1] == 1:
        return "fwd"
    if len(outs) == 1:
        return "dq"
    if len(outs) == 2 and outs[0][1] == outs[1][1]:
        return "dkv"
    return None


def kernel_kinds(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> kernel kind, for every flash kernel in the
    compiled program's HLO text; trace events carry the instruction name."""
    out = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        kind = _kind_of(line)
        if kind:
            out[op_name(line)] = kind
    return out


def op_name(name: str) -> str:
    """The instruction name of a device event, which the trace gives either
    bare (``fusion.12``) or as the instruction's HLO text
    (``%fusion.12 = f32[...] fusion(...), ...``)."""
    return name.split(" ", 1)[0].lstrip("%")


def flash_kind(event: Event, kinds: Dict[str, str]) -> Optional[str]:
    """The kernel kind of a trace event: by its instruction name in
    ``kinds``, else by the HLO text the event is named by."""
    return kinds.get(op_name(event[0])) or _kind_of(event[0])


def kernel_time(events: Sequence[Event], want: Sequence[str], lo: float, hi: float,
                kinds: Dict[str, str]) -> Tuple[int, float]:
    """``(number of calls, summed device ns)`` of the flash kernels of the
    kinds in ``want`` that start inside ``[lo, hi)``."""
    hits = [e for e in events if lo <= e[1] < hi and flash_kind(e, kinds) in want]
    return len(hits), sum(e[2] for e in hits)


# -------------------------------------------------------------- breakdown


def top_ops(events: Sequence[Event], lo: float, hi: float, kinds: Dict[str, str],
            n: int = 10) -> List[list]:
    """The ``n`` operations with most device time in the window, by name
    with the trailing instance number dropped (``fusion.12`` -> ``fusion``),
    except kernels, which are named by their kind.  A loop or call whose
    event spans the operations inside it is left out, not counted twice."""
    tot: Dict[str, float] = {}
    for e in events:
        if lo <= e[1] < hi and not CONTAINER.match(op_name(e[0])):
            kind = flash_kind(e, kinds)
            key = f"flash_{kind}" if kind else re.sub(r"[.][0-9]+$", "", op_name(e[0]))
            tot[key] = tot.get(key, 0.0) + e[2]
    return [[k, v * 1e-9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: Sequence[Event], spans: Sequence[Event], lo: float, hi: float,
              n: int = 10) -> List[list]:
    """The ``n`` longest stretches of ``[lo, hi)`` with no device operation,
    each named by the innermost benchmark span open at its middle."""
    busy = union(clip(events, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        open_ = [(s, name) for name, s, d in spans if s <= mid < s + d]
        out.append([max(open_)[1] if open_ else "outside any span", (b - a) * 1e-9])
    return out
