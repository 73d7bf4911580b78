"""Reduction of a profiler trace to device busy time, idle share, the
device operations that took most time, and the longest idle gaps named
by what the benchmark's client was doing in them.

A trace is reduced from a plain structure, so that a test can build one
by hand: a list of planes ``(plane_name, [(line_name, [(event_name,
start_ns, duration_ns), ...]), ...])``.  :func:`load` makes that
structure from the ``.xplane.pb`` file that ``jax.profiler`` writes.

Device planes are named ``/device:TPU:<n>``; on each, the ``XLA Ops``
line holds one event per operation that ran, and the ``XLA Modules``
line one event per program.  The benchmark's own spans are
``jax.profiler.TraceAnnotation``s whose names start with ``bench.``;
they sit on the lines of the ``/host:CPU`` plane, on the same clock.
``bench.window`` spans the measured window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
Line = Tuple[str, List[Event]]
Plane = Tuple[str, List[Line]]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "bench."
WINDOW = "bench.window"
#: entries of each breakdown list
TOP = 10


@dataclass
class TraceSummary:
    window_s: float
    #: seconds in which an operation ran, averaged over the devices
    busy_s: float
    devices: int
    #: [[module/op, seconds]] summed over the window, longest first
    device_ops: List[List] = field(default_factory=list)
    #: [[what the client was doing, seconds]], the longest single gaps
    idle_gaps: List[List] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load(directory: str) -> List[Plane]:
    """The planes of the one ``.xplane.pb`` file under ``directory``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {directory}, found {paths}")
    data = ProfileData.from_file(paths[0])
    return [
        (plane.name, [
            (line.name, [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                         for ev in line.events])
            for line in plane.lines
        ])
        for plane in data.planes
    ]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Disjoint, sorted cover of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _op_name(event_name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()[:80]


def _module_name(event_name: str) -> str:
    """``jit_run(1234)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()[:80]


def _lines(plane: Plane) -> Dict[str, List[Event]]:
    return {name: events for name, events in plane[1]}


def annotations(planes: Sequence[Plane]) -> List[Event]:
    """The benchmark's spans, from every host thread."""
    return [
        ev
        for name, lines in planes if name == HOST_PLANE
        for _, events in lines
        for ev in events if ev[0].startswith(ANNOTATION_PREFIX)
    ]


def reduce(planes: Sequence[Plane]) -> TraceSummary:
    """Busy time, top operations and labelled idle gaps in the window."""
    spans = annotations(planes)
    windows = [ev for ev in spans if ev[0] == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(windows)}")
    _, lo, dur = windows[0]
    hi = lo + dur
    labelled = [(ev[1], ev[1] + ev[2], ev[0][len(ANNOTATION_PREFIX):])
                for ev in spans if ev[0] != WINDOW]

    devices = [p for p in planes if DEVICE_PLANE.match(p[0])]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    busy_total = 0.0
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, float, float]] = []
    for i, plane in enumerate(devices):
        lines = _lines(plane)
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        covered = _clip(union([(s, s + d) for _, s, d in ops]), lo, hi)
        busy_total += sum(e - s for s, e in covered)
        modules = sorted((s, s + d, _module_name(n))
                         for n, s, d in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        for name, s, d in ops:
            clipped = min(s + d, hi) - max(s, lo)
            if clipped <= 0:
                continue
            j = bisect.bisect_right(starts, s) - 1
            owner = modules[j][2] if j >= 0 and s < modules[j][1] else ""
            key = f"{owner}/{_op_name(name)}" if owner else _op_name(name)
            op_time[key] = op_time.get(key, 0.0) + clipped
        if i == 0:  # gaps are named on the first device
            edges = [lo] + [x for iv in covered for x in iv] + [hi]
            for gs, ge in zip(edges[::2], edges[1::2]):
                if ge > gs:
                    gaps.append((ge - gs, gs, ge))
    gaps.sort(key=lambda g: -g[0])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / len(devices) * 1e-9,
        devices=len(devices),
        device_ops=[[k, v * 1e-9] for k, v in
                    sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        idle_gaps=[[_doing(labelled, gs, ge), g * 1e-9] for g, gs, ge in gaps[:TOP]],
    )


def _doing(labelled: Sequence[Tuple[float, float, str]], lo: float, hi: float) -> str:
    """What the client was doing at the middle of ``[lo, hi)``: the
    benchmark spans open then, with a count where several are."""
    mid = (lo + hi) / 2
    open_now: Dict[str, int] = {}
    for s, e, label in labelled:
        if s <= mid < e:
            open_now[label] = open_now.get(label, 0) + 1
    if not open_now:
        return "no request open"
    return " + ".join(f"{n}x {label}" if n > 1 else label
                      for label, n in sorted(open_now.items()))
