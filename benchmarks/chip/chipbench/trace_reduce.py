"""From a profiler trace to device time, idle time and a breakdown.

The JAX profiler writes an ``.xplane.pb``; :func:`load` turns it into
plain :class:`Plane` / :class:`Line` / :class:`Event` records (times in
nanoseconds), and everything else here works on those records, so the
tests can build a trace by hand.

- The traced window is the host span named :data:`WINDOW` that the
  benchmark opens around its measured loop.
- Device operations are the events of each chip plane's ``XLA Ops``
  line, clipped to the window.
- Busy time is the union of a device's operation intervals; idle share
  is 1 - busy / window, averaged over the devices.
- An idle gap is labelled by the benchmark's host span (``bench.step``,
  ``bench.submit``) that overlaps it most, ``host`` where none does.
"""
from __future__ import annotations

import glob
import math
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Line:
    name: str
    events: List[Event]


@dataclass
class Plane:
    name: str
    lines: List[Line]


def load(trace_dir: str) -> List[Plane]:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    planes = []
    for p in pd.planes:
        lines = []
        for ln in p.lines:
            evs = [Event(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in ln.events]
            lines.append(Line(ln.name, evs))
        planes.append(Plane(p.name, lines))
    return planes


_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


def device_planes(planes: List[Plane]) -> List[Plane]:
    """One plane per chip (``/device:TPU:0``...); planes such as
    ``/device:CUSTOM:...`` are not chips."""
    return [p for p in planes if _DEVICE.match(p.name)]


def op_events(plane: Plane) -> List[Event]:
    return [e for ln in plane.lines if ln.name == OP_LINE
            for e in ln.events if e.dur_ns > 0]


def host_spans(planes: List[Plane]) -> List[Event]:
    return [e for p in planes if not p.name.startswith("/device:")
            for ln in p.lines for e in ln.events
            if e.name.startswith(SPAN_PREFIX)]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(e: Event, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    a, b = max(e.start_ns, lo), min(e.end_ns, hi)
    return (a, b) if b > a else None


@dataclass
class Summary:
    window_s: float
    busy_s: float                        # mean over devices
    n_devices: int
    ops: List[Tuple[str, float, Optional[str]]]   # (name, seconds, kind)
    idle_gaps: List[Tuple[str, float]]   # (host label, seconds), longest first

    def op_seconds(self, kind: str) -> Optional[float]:
        """Device seconds (summed over devices, divided by their count)
        of operations of ``kind`` (:func:`op_kind`); None where no
        operation is of that kind."""
        hits = [s for _, s, k in self.ops if k == kind]
        return sum(hits) / self.n_devices if hits else None

    def top_ops(self, n: int = 10) -> List[list]:
        tot: Dict[str, float] = {}
        for name, s, _ in self.ops:
            tot[name] = tot.get(name, 0.0) + s / self.n_devices
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
                [:n]]


def summarize(planes: List[Plane]) -> Summary:
    spans = host_spans(planes)
    win = [e for e in spans if e.name == WINDOW]
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device plane")
    if not win:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    per_dev = [op_events(p) for p in devs]
    lo, hi = win[0].start_ns, win[0].end_ns
    ops, busy, gaps = [], [], []
    labels = [e for e in spans if e.name != WINDOW]
    for evs in per_dev:
        clipped = []
        for e in evs:
            c = _clip(e, lo, hi)
            if c is not None:
                clipped.append(c)
                ops.append((short_name(e.name), (c[1] - c[0]) * 1e-9,
                            op_kind(e.name)))
        merged = union(clipped)
        busy.append(sum(b - a for a, b in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((_label(labels, a, b), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9, n_devices=len(devs),
                   ops=ops, idle_gaps=gaps)


def _label(spans: List[Event], a: float, b: float) -> str:
    best, best_overlap = "host", 0.0
    for e in spans:
        o = min(b, e.end_ns) - max(a, e.start_ns)
        if o > best_overlap:
            best, best_overlap = e.name, o
    return best


# -- what an operation does, from its HLO text ------------------------------
# A TPU trace names each operation by its HLO instruction, e.g.
#   %fusion.155 = f32[91750400]{...} fusion(f32[8,5120]{...} %x,
#       s32[91750400]{...} %idx), kind=kCustom, calls=%fused_computation.4
# Gathers and scatters are fused into kCustom fusions, so they are told
# apart by their operands: a gather reads one index per element it
# returns; a scatter writes an update per index into a larger result, and
# its indices are first sorted with their updates.
_TYPED = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_OP = re.compile(r"=\s*(\([^=]*?\)|\S+)\s+([a-z][\w-]*)\(")
_INT = ("s8", "s16", "s32", "s64", "u8", "u16", "u32", "u64")


def _arrays(text: str) -> List[Tuple[str, int]]:
    return [(t, math.prod(int(d) for d in dims.split(",") if d))
            for t, dims in _TYPED.findall(text)]


def hlo_parts(text: str):
    """(op, result arrays, operand arrays) of an HLO instruction's text,
    each array as (element type, element count); None if unparsable."""
    m = _OP.search(text)
    if m is None:
        return None
    depth, i = 0, m.end() - 1
    for j in range(i, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            break
    return m.group(2), _arrays(m.group(1)), _arrays(text[i:j + 1])


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(text: str, width: int = 160) -> str:
    """An operation's HLO text without layouts, at most ``width`` long."""
    prev = None
    while prev != text:
        prev, text = text, _LAYOUT.sub("", text)
    return text[:width]


def op_kind(text: str) -> Optional[str]:
    """"gather", "scatter" or None for one operation's HLO text."""
    parts = hlo_parts(text)
    if parts is None:
        return None
    op, result, operands = parts
    if op in ("gather", "scatter"):
        return op
    ints = [n for t, n in operands if t in _INT]
    floats = [n for t, n in operands if t.startswith(("f", "bf"))]
    if op == "sort" and ints and floats and set(ints) & set(floats):
        return "scatter"
    if op != "fusion" or "kind=kCustom" not in text or len(result) != 1:
        return None
    out = result[0][1]
    if out in ints:
        return "gather"
    if any(n < out and n in floats for n in ints):
        return "scatter"
    return None
