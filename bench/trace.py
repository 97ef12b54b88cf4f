"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The profiler writes an ``.xplane.pb`` file; :func:`load_xplane` turns it
into a plain, JSON-able structure (planes -> lines -> events), which is
what every other function here reads.  A small trace kept in that form
is the fixture of the CPU tests.

Events are ``[name, start_ns, duration_ns, stats]``.  Device planes are
named ``/device:TPU:<n>``; on them the line ``XLA Ops`` holds one event
per executed operation and ``XLA Modules`` one per program run.  Host
threads live on the plane ``/host:CPU``; the benchmark's own
``TraceAnnotation`` spans appear there under their names.
"""

from __future__ import annotations

import bisect
import glob
import math
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def load_xplane(trace_dir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` into plain data."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = []
            for e in line.events:
                stats = {}
                for k, v in e.stats:
                    if isinstance(v, (int, float, str)):
                        stats[k] = v
                evs.append([e.name, float(e.start_ns), float(e.duration_ns),
                            stats])
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PREFIX)]


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def op_events(plane: dict) -> List[list]:
    """Every operation the device ran (the ``XLA Ops`` line)."""
    return _line(plane, OPS_LINE)


def module_events(plane: dict) -> List[list]:
    """Every program run on the device (the ``XLA Modules`` line)."""
    return _line(plane, MODULES_LINE)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping ``(start, end)`` intervals, sorted by start."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def busy_ns(plane: dict, lo: float, hi: float) -> float:
    """Nanoseconds in ``[lo, hi)`` in which any operation ran."""
    ops = [(e[1], e[1] + e[2]) for e in op_events(plane)]
    return total(clip(union(ops), lo, hi))


def idle_gaps(plane: dict, lo: float, hi: float) -> List[Interval]:
    """The stretches of ``[lo, hi)`` in which no operation ran."""
    busy = clip(union((e[1], e[1] + e[2]) for e in op_events(plane)),
                lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def op_name(raw: str) -> str:
    """An operation's stable name: ``%paged_attention.6 = bf16[...] ...``
    becomes ``paged_attention`` (the HLO instruction name, without the
    ``%`` and the number XLA appends)."""
    name = raw.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def self_times(events: Sequence[list]) -> List[Tuple[str, float, float]]:
    """Each event's (name, start, self time): its duration less that of
    the events nested in it on the same line (a ``while`` op spans every
    op of its loop body)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    selft = [float(e[2]) for e in events]
    stack: List[int] = []
    for i in order:
        s, d = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            selft[stack[-1]] -= d
        stack.append(i)
    return [(op_name(e[0]), e[1], max(selft[i], 0.0))
            for i, e in enumerate(events)]


def op_time_by_name(plane: dict, lo: float, hi: float) -> Dict[str, float]:
    """Device self time per operation name, of the ops that started
    inside ``[lo, hi)``."""
    out: Dict[str, float] = {}
    for name, s, t in self_times(op_events(plane)):
        if lo <= s < hi:
            out[name] = out.get(name, 0.0) + t
    return out


def kernel_ns(plane: dict, kernel: str, lo: float, hi: float) -> float:
    """Device time of one kernel by its stable name (the name passed to
    ``pallas_call``)."""
    return sum(e[2] for e in op_events(plane)
               if op_name(e[0]) == kernel and lo <= e[1] < hi)


def kernel_calls(plane: dict, kernel: str, lo: float, hi: float) -> int:
    return sum(1 for e in op_events(plane)
               if op_name(e[0]) == kernel and lo <= e[1] < hi)


def ops_inside(plane: dict, module_prefix: str, lo: float, hi: float,
               inside: bool = True) -> float:
    """Device self time of the operations that started inside (or
    outside) the runs of programs whose name starts with
    ``module_prefix``, within ``[lo, hi)``."""
    mods = union((e[1], e[1] + e[2]) for e in module_events(plane)
                 if e[0].startswith(module_prefix))
    starts = [m[0] for m in mods]
    t = 0.0
    for _, s, d in self_times(op_events(plane)):
        if not lo <= s < hi:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if (i >= 0 and s < mods[i][1]) == inside:
            t += d
    return t


def host_spans(trace: dict, names: Sequence[str]) -> List[Tuple[str, float,
                                                                float]]:
    """The host spans whose name is one of ``names``: (name, start, end)."""
    want = set(names)
    out = []
    for p in trace["planes"]:
        if p["name"] != HOST_PLANE:
            continue
        for line in p["lines"]:
            for name, s, d, _ in line["events"]:
                if name in want:
                    out.append((name, s, s + d))
    return out


def attribute_gaps(gaps: Sequence[Interval],
                   spans: Sequence[Tuple[str, float, float]]
                   ) -> Dict[str, float]:
    """Idle nanoseconds by what the host was doing: each gap goes to the
    innermost span (the shortest) that covers its midpoint, or to
    ``"no span"`` where none does."""
    out: Dict[str, float] = {}
    for s, e in gaps:
        mid = (s + e) / 2
        best: Optional[Tuple[str, float, float]] = None
        for sp in spans:
            if sp[1] <= mid < sp[2] and (
                    best is None or sp[2] - sp[1] < best[2] - best[1]):
                best = sp
        key = best[0] if best else "no span"
        out[key] = out.get(key, 0.0) + (e - s)
    return out


def top(d: Dict[str, float], n: int = 10, scale: float = 1e-9
        ) -> List[list]:
    """The ``n`` largest entries as ``[[name, value * scale], ...]``."""
    return [[k, v * scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def percentile(samples: Sequence[float], q: float) -> float:
    """The ``q``-th percentile of the exact samples (nearest rank: the
    smallest sample with at least ``q`` percent of them at or below)."""
    if not samples:
        return math.nan
    xs = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])
