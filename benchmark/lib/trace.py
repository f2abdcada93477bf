"""Reduction of a JAX profiler trace to device busy time, idle gaps,
kernel calls and the benchmark's own host spans.

The profiler writes one ``.xplane.pb``.  In it, each chip is a plane
named ``/device:TPU:<n>`` whose ``XLA Ops`` line holds one event per
operation that ran (``XLA Modules`` holds one per program launch), and
the host is the plane ``/host:CPU``, whose thread lines carry the
``jax.profiler.TraceAnnotation`` spans the benchmark opens around each
call into the system.  Both are on one clock.  Busy time is the union
of a chip's operation intervals inside the traced window (the
benchmark's ``bench.window`` span); idle gaps are what is left, each
labelled with the innermost benchmark span open at its midpoint.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

Event = Tuple[float, float, str]          # (start_ns, end_ns, name)


@dataclass
class Trace:
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)
    window: Optional[Tuple[float, float]] = None


def load(path: str, chips: int = 1) -> Trace:
    """Read the device planes of the first ``chips`` chips and the
    benchmark's host spans from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m and int(m.group(1)) < chips:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    tr.ops[dev] = _events(line)
                elif line.name == "XLA Modules":
                    tr.modules[dev] = _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                tr.spans.extend(e for e in _events(line)
                                if e[2].startswith(SPAN_PREFIX))
    wins = [e for e in tr.spans if e[2] == WINDOW_SPAN]
    if wins:
        tr.window = (min(e[0] for e in wins), max(e[1] for e in wins))
    return tr


def _events(line) -> List[Event]:
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in line.events]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def busy_ns(tr: Trace, dev: int) -> float:
    """Nanoseconds of the traced window in which an operation ran."""
    lo, hi = tr.window
    busy = _clip(union([(a, b) for a, b, _ in tr.ops.get(dev, [])]),
                 lo, hi)
    return sum(b - a for a, b in busy)


def idle_gaps(tr: Trace, dev: int) -> List[Tuple[float, float]]:
    lo, hi = tr.window
    busy = _clip(union([(a, b) for a, b, _ in tr.ops.get(dev, [])]),
                 lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def span_at(tr: Trace, t: float) -> str:
    """The innermost benchmark span open at ``t`` (the window itself
    when no other is)."""
    open_ = [e for e in tr.spans if e[0] <= t < e[1]]
    if not open_:
        return "outside any benchmark span"
    return min(open_, key=lambda e: e[1] - e[0])[2]


def op_label(name: str) -> str:
    """An XLA op's instruction text cut to its name and result shape."""
    head = name.split(" = ", 1)
    if len(head) == 2:
        shape = head[1].split(" ", 1)[0]
        return f"{head[0]} {shape}"[:100]
    return name[:100]


def breakdown(tr: Trace, dev: int = 0, top: int = 10) -> Dict:
    """The device operations that took most time in the window, and
    the longest idle gaps labelled by what the host was doing."""
    lo, hi = tr.window
    by_op: Dict[str, float] = {}
    for a, b, name in _leaves(tr.ops.get(dev, [])):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            key = op_label(name)
            by_op[key] = by_op.get(key, 0.0) + (b - a) / 1e9
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(tr, dev), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[span_at(tr, (a + b) / 2), (b - a) / 1e9]
                          for a, b in gaps]}


def _leaves(events: List[Event]) -> List[Event]:
    """The operations that hold no other (a loop's body operations
    are events inside the loop's own event)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1][0] >= e[1]]


def module_ns(tr: Trace, dev: int, prefix: str) -> float:
    """Device nanoseconds, inside the window, of program launches whose
    name starts with ``prefix`` (``jit_<function>``)."""
    lo, hi = tr.window
    return sum(max(0.0, min(b, hi) - max(a, lo))
               for a, b, name in tr.modules.get(dev, [])
               if name.startswith(prefix))


_GF_CALL = re.compile(
    r"= u8\[(\d+),(\d+)\]\S* custom-call\(s8\[(\d+),(\d+)\]\S* %[^,]+, "
    r"u8\[(\d+),(\d+)\]\S* %[^)]+\).*tpu_custom_call")


def gf_kernel_calls(tr: Trace, dev: int = 0) -> List[Tuple[float, int,
                                                            int, int]]:
    """Every call, inside the window, of a Pallas kernel with the GF
    bit-matrix signature u8[r_out, L] = f(s8[8 r_out, 8 r_in],
    u8[r_in, L]): (device ns, r_in, r_out, L)."""
    lo, hi = tr.window
    out = []
    for a, b, name in tr.ops.get(dev, []):
        m = _GF_CALL.search(name)
        if not m or b <= lo or a >= hi:
            continue
        r_out, lanes, a8, b8, r_in, lanes_in = map(int, m.groups())
        if a8 == 8 * r_out and b8 == 8 * r_in and lanes == lanes_in:
            out.append((min(b, hi) - max(a, lo), r_in, r_out, lanes))
    return out
