"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* the traced window: the host span named ``window`` that the harness puts
  around the measured window;
* device busy time: the union of the intervals in which an operation ran
  on a device (the ``XLA Ops`` line of each ``/device:<kind>:<n>`` plane),
  clipped to the window and averaged over the devices;
* a kernel's device time and event count: the operations whose name, or
  any of whose string stats, contains the kernel's name;
* the device operations that took most time, and the device's idle time in
  the window split by what the host was doing: the innermost host span
  open at that moment, or ``no_span`` (the open loop waiting for the next
  arrival).  The host spans are the harness's (``HOST_SPANS``) and those
  the caller names, such as the spans the program's own tracer recorded in
  the window, each of which opens a profiler annotation of its name.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
NO_SPAN = "no_span"
#: Host spans the harness records (see ``probes.py`` and ``run.py``).
HOST_SPANS = ("pump", "plane_pass", "decide", "candidate_build",
              "reorg_swaps", "materialize")

Interval = Tuple[float, float]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: int
    busy_s: float                       # averaged over devices
    kernel_s: float                     # summed over devices
    kernel_events: int
    device_ops: List[Tuple[str, float]]
    idle_by_host: List[Tuple[str, float]]


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` within ``[lo, hi]``."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def innermost(spans: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Cut nested spans into disjoint segments, each named by the
    innermost span open over it (``NO_SPAN`` where none is)."""
    bounds = sorted({t for a, b, _ in spans for t in (a, b)})
    starts = sorted(spans, key=lambda s: (s[0], -s[1]))
    out, stack, k = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(starts) and starts[k][0] <= a:
            stack.append(starts[k])
            k += 1
        while stack and stack[-1][1] <= a:
            stack.pop()
        live = [s for s in stack if s[1] > a]
        stack = live
        out.append((a, b, live[-1][2] if live else NO_SPAN))
    return out


def attribute(idle: Sequence[Interval],
              segments: Sequence[Tuple[float, float, str]],
              lo: float, hi: float) -> Dict[str, float]:
    """Idle time per host-span name (ns)."""
    segs = list(segments)
    # Fill the window around the spans with NO_SPAN.
    filled, at = [], lo
    for a, b, name in segs:
        if a > at:
            filled.append((at, a, NO_SPAN))
        filled.append((a, b, name))
        at = max(at, b)
    if hi > at:
        filled.append((at, hi, NO_SPAN))
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for a, b in idle:
        while j < len(filled) and filled[j][1] <= a:
            j += 1
        k = j
        while k < len(filled) and filled[k][0] < b:
            s, e, name = filled[k]
            out[name] += min(b, e) - max(a, s)
            k += 1
    return dict(out)


def op_name(name: str) -> str:
    """An HLO op's event name without its operands and layouts:
    ``"%copy.1 = f32[16,8]{1,0:T(8,128)} copy(...)"`` -> ``"copy.1
    f32[16,8]"``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    return f"{lhs.lstrip('%')} {re.sub(r'{[^}]*}', '', rhs.split(' ', 1)[0])}"


def _mentions(event, name: str) -> bool:
    if name in event.name:
        return True
    return any(isinstance(v, str) and name in v for _, v in event.stats)


def reduce_trace(path: str, kernel: str = "decision_fused", top: int = 10,
                 spans: Iterable[str] = ()) -> Optional[TraceSummary]:
    """The numbers of one trace; None where it holds no ``window`` span.
    ``spans`` names host spans admitted besides ``HOST_SPANS``."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, kernel, top,
                         spans)


def reduce_planes(planes, kernel: str = "decision_fused", top: int = 10,
                  spans: Iterable[str] = ()) -> Optional[TraceSummary]:
    """:func:`reduce_trace` over a trace's planes (objects with the
    ``name``, ``lines`` and ``events`` that ``ProfileData`` gives)."""
    names = set(HOST_SPANS).union(spans)
    window: Optional[Interval] = None
    host: List[Tuple[float, float, str]] = []
    device_lines = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            device_lines += [ln for ln in plane.lines if ln.name == OPS_LINE]
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name in names:
                    host.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        return None
    lo, hi = window
    busy_ns, kernel_ns, kernel_events = 0.0, 0.0, 0
    by_op: Dict[str, float] = collections.defaultdict(float)
    idle_total: Dict[str, float] = collections.defaultdict(float)
    segments = innermost(clip_spans(host, lo, hi))
    for line in device_lines:
        ops = []
        for ev in line.events:
            a, b = max(ev.start_ns, lo), min(ev.end_ns, hi)
            if b <= a:
                continue
            ops.append((a, b))
            by_op[op_name(ev.name)] += b - a
            if _mentions(ev, kernel):
                kernel_ns += b - a
                kernel_events += 1
        busy = union(ops)
        busy_ns += sum(b - a for a, b in busy)
        for name, ns in attribute(gaps(busy, lo, hi), segments,
                                  lo, hi).items():
            idle_total[name] += ns
    n_dev = len(device_lines)
    scale = 1e-9 / max(n_dev, 1)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        devices=n_dev,
        busy_s=busy_ns * scale,
        kernel_s=kernel_ns * 1e-9,
        kernel_events=kernel_events,
        device_ops=sorted(((k, v * 1e-9) for k, v in by_op.items()),
                          key=lambda kv: -kv[1])[:top],
        idle_by_host=sorted(((k, v * scale) for k, v in idle_total.items()),
                            key=lambda kv: -kv[1])[:top])


def clip_spans(spans: Sequence[Tuple[float, float, str]], lo: float,
               hi: float) -> List[Tuple[float, float, str]]:
    return [(max(a, lo), min(b, hi), n) for a, b, n in spans
            if b > lo and a < hi]


def inventory(path: str) -> List[str]:
    """Plane and line names with event counts and a few event names, for
    looking at a trace by hand."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs).most_common(5)
            stats = list(evs[0].stats)[:6] if evs else []
            out.append(f"  line {line.name!r}: {len(evs)} events; top "
                       f"{names}; first stats {stats}")
    return out
