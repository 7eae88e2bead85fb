"""From a profiler trace to device busy time, idle share and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote: the
operations of each TPU device plane, and the benchmark's own host spans
(``bench.*`` TraceAnnotations).  The reduction below is plain interval
arithmetic on (start_ns, end_ns, name) triples, so that every PR computes
the same number the same way (``benchmark/tests/test_tracefile.py``
checks each number by hand on a small recorded trace).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[float, float, str]  # start_ns, end_ns, name

# The device plane's line that holds one event per XLA operation (a
# Pallas kernel is one operation, a custom call).
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPAN = "bench."


def find_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def load(path: str):
    """(device ops by plane name, XLA programs by plane name, host
    ``bench.*`` spans)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                into = {OPS_LINE: devices, MODULES_LINE: modules}.get(
                    line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events
                            if e.name.startswith(HOST_SPAN))
    return devices, modules, host


# -- reduction ---------------------------------------------------------


def merged(intervals: Sequence[Event], lo: float, hi: float):
    """The union of the intervals, clipped to [lo, hi], as sorted
    disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals: Sequence[Event], lo: float, hi: float) -> float:
    """Time within [lo, hi] in which some operation ran."""
    return sum(e - s for s, e in merged(intervals, lo, hi))


def idle_share(intervals: Sequence[Event], lo: float, hi: float) -> float:
    return 1.0 - busy_ns(intervals, lo, hi) / (hi - lo)


def kernel_ns(intervals: Sequence[Event], pattern: str, lo: float,
              hi: float) -> Tuple[float, int]:
    """Summed duration (clipped to the window) and count of the events
    whose name matches ``pattern``."""
    rx = re.compile(pattern)
    total, count = 0.0, 0
    for s, e, name in intervals:
        if rx.search(name):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                total += e - s
                count += 1
    return total, count


def named_by_module(ops: Sequence[Event], modules: Sequence[Event]):
    """Each operation renamed ``<program>/<op>``: the XLA program whose
    run contains it (its id dropped) and the operation's own name (the
    HLO instruction's name, before its text).  The trace names an op by
    its whole HLO line, and ``%fusion`` recurs in every program."""
    progs = sorted(modules)
    starts = [m[0] for m in progs]
    out = []
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = "?"
        if i >= 0 and progs[i][1] >= e:
            prog = progs[i][2].split("(")[0]
        out.append((s, e, f"{prog}/{name.split(' = ')[0]}"))
    return out


def top_ops(intervals: Sequence[Event], lo: float, hi: float, n: int = 10):
    """[[name, seconds]] of the operations that took most time."""
    by: Dict[str, float] = {}
    for s, e, name in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            by[name] = by.get(name, 0.0) + (e - s)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(intervals: Sequence[Event], spans: Sequence[Event],
              lo: float, hi: float, n: int = 10):
    """[[what the host was doing, seconds]]: the device's idle time in
    [lo, hi], each gap charged to the innermost host span that covers
    its midpoint (``"none"`` where none does), summed by span name."""
    busy = merged(intervals, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    by: Dict[str, float] = {}
    for gs, ge in gaps:
        mid = (gs + ge) / 2
        cover = [(e - s, name) for s, e, name in spans if s <= mid < e]
        label = min(cover)[1] if cover else "none"
        by[label] = by.get(label, 0.0) + (ge - gs)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]
