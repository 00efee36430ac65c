"""Reduction of a profiler trace to device busy and idle time, per-program
device time and a breakdown.

The reduction works on a flat list of events, ``(plane, line, name,
start_ns, dur_ns, module)``, so that it can be checked on a small recorded
trace (``bench/tests/data``) without the profiler.  ``load_xplane`` reads
that list from the ``.xplane.pb`` that ``jax.profiler`` writes.

* Device planes are ``/device:TPU:<n>``.  Busy time is the union of the
  intervals of the events on a device's ``XLA Ops`` line, clipped to the
  window, averaged over the devices; the idle share is 1 - busy / window.
* Per-program time sums the events of the ``XLA Modules`` line by program
  name, with the ``(<id>)`` suffix the runtime appends taken off.
* The window is the host span ``bench.window`` where the trace has one.
* Each idle gap of the first device is put down to the innermost
  ``bench.*`` host span that covers most of it (``host`` where none does).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_PROGRAM_ID = re.compile(r"\(\d+\)$")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""


def load_xplane(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[Event] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        keep_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if not keep_device and not name.startswith(SPAN_PREFIX):
                    continue
                module = ""
                if keep_device and line.name == "XLA Ops":
                    for k, v in ev.stats:
                        if k == "hlo_module":
                            module = str(v)
                            break
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 module))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def program_name(name: str) -> str:
    return _PROGRAM_ID.sub("", name)


def summarize(events: Iterable[Event], top: int = 10) -> Dict:
    """Busy and window seconds, per-program device seconds and counts, and
    the ``breakdown`` of the result line."""
    events = list(events)
    spans = [e for e in events if not DEVICE_PLANE.match(e.plane)
             and e.name.startswith(SPAN_PREFIX)]
    dev = [e for e in events if DEVICE_PLANE.match(e.plane)]
    planes = sorted({e.plane for e in dev})
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    win = [e for e in spans if e.name == WINDOW_SPAN]
    if win:
        lo = min(e.start_ns for e in win)
        hi = max(e.start_ns + e.dur_ns for e in win)
    else:
        lo = min(e.start_ns for e in dev)
        hi = max(e.start_ns + e.dur_ns for e in dev)

    def ops_of(plane: str) -> List[Event]:
        evs = [e for e in dev if e.plane == plane and e.line == "XLA Ops"]
        return evs or [e for e in dev if e.plane == plane
                       and e.line == "XLA Modules"]

    busy_ns, first_union = [], None
    for plane in planes:
        iv = [c for c in (_clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
                          for e in ops_of(plane)) if c]
        u = _union(iv)
        busy_ns.append(sum(e - s for s, e in u))
        if first_union is None:
            first_union = u

    programs: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    for e in dev:
        if e.line != "XLA Modules":
            continue
        c = _clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
        if c:
            p = programs[program_name(e.name)]
            p[0] += (c[1] - c[0]) / len(planes) * 1e-9
            p[1] += 1

    op_time: Dict[str, float] = collections.defaultdict(float)
    for e in ops_of(planes[0]):
        c = _clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
        if c:
            key = f"{program_name(e.module)}/{e.name}" if e.module else e.name
            op_time[key] += (c[1] - c[0]) * 1e-9

    gaps, t = [], lo
    for s, e in first_union:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    idle_by: Dict[str, float] = collections.defaultdict(float)
    inner = [s for s in spans if s.name != WINDOW_SPAN]
    by_start = sorted(range(len(inner)), key=lambda i: inner[i].start_ns)
    active: List[int] = []        # spans begun before the gap's end
    j = 0
    for gs, ge in gaps:           # the gaps come in order of time
        while j < len(by_start) and inner[by_start[j]].start_ns < ge:
            active.append(by_start[j])
            j += 1
        active = [i for i in active
                  if inner[i].start_ns + inner[i].dur_ns > gs]
        # most overlap wins; of spans that cover the gap alike, the
        # innermost (shortest) one, and of those the first
        best, best_key = (0.0, 0.0), "host"
        for s in (inner[i] for i in sorted(active)):
            c = _clip(s.start_ns, s.start_ns + s.dur_ns, gs, ge)
            if c and (c[1] - c[0], -s.dur_ns) > best:
                best, best_key = (c[1] - c[0], -s.dur_ns), s.name
        idle_by[best_key] += (ge - gs) * 1e-9

    def top_of(d: Dict[str, float]) -> List[List]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "busy_s": sum(busy_ns) / len(planes) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "devices": len(planes),
        "programs": {k: (v[0], int(v[1])) for k, v in programs.items()},
        "breakdown": {"device_ops": top_of(op_time),
                      "idle_gaps": top_of(idle_by)},
    }


def program_seconds(summary: Dict, pattern: str) -> Tuple[float, int]:
    """Device seconds and launches of the programs whose name matches
    ``pattern`` (a regular expression searched in the name)."""
    rx = re.compile(pattern)
    secs, n = 0.0, 0
    for name, (s, c) in summary["programs"].items():
        if rx.search(name):
            secs += s
            n += c
    return secs, n
