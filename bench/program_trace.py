"""The program's own spans and scopes in a profiler trace, on the device's
clock.

``bench/trace.py`` reduces a trace with the harness's ``bench.*`` spans,
which wrap calls from outside the program.  The program marks its layers
itself (``repro.spans``): host spans named ``repro.*`` whose arguments
(``nbytes``, ``program``, ``rid``, ...) are stats of the trace event, and
``jax.named_scope``s on the ops of its device programs.  ``load_events``
keeps both besides what ``bench.trace.load_xplane`` keeps, and
``summarize`` returns every key of ``bench.trace.summarize``, computed by
it and so unchanged, plus:

* ``clock_offset_ns``: the device clock less the host clock.  A program
  starts on the device after the host span that launched it began, and
  ends before the host span that waited for it ended; pairing the k-th
  launch (``repro.fl.flush.launch``, ``repro.serve.prefill``) and the
  k-th wait (``repro.fl.flush.wait``, ``repro.serve.first_token``) of a
  program, named by their ``program`` argument, with its k-th launch on
  the device bounds the offset from above and below.
  ``clock_offset_interval_ns`` holds the bounds (``None`` where no pair
  gives one); the offset is the interval's midpoint, and 0 where the
  interval is empty.
* ``spans``: for each ``repro.*`` span in the window, its ``count``,
  ``seconds``, ``self_s`` (less the ``repro.*`` spans nested in it on its
  thread) and ``args``, the sum of each numeric argument.
* ``scopes``: device seconds of each program's ops by the first part of
  their named-scope path, as the union of the ops' intervals, so that a
  loop and the ops of its body count once.  An op's path is the
  ``op_name`` of its HLO instruction less the ``jit(...)`` parts: the
  trace's op events carry no ``op_name``, but its ``/host:metadata``
  plane holds each program's optimized HLO (``hlo_op_names``), whose
  instructions the op events name.  Ops without one count under ``""``.
* ``breakdown["idle_self"]``: each idle gap of the first device, on the
  host's clock, cut at every span's start and end; each piece goes to
  the innermost ``bench.*`` or ``repro.*`` span covering it (the
  shortest), or to ``host`` where none does.  The pieces sum to the
  window less the device's busy time on the host's clock.
"""
from __future__ import annotations

import bisect
import collections
import glob
import math
import os
import re
from types import MappingProxyType
from typing import Any, Dict, Iterable, List, Mapping, NamedTuple, Optional, Tuple

from bench import trace as tr

HOST_PREFIXES = (tr.SPAN_PREFIX, "repro.")
PROGRAM_PREFIX = "repro."
LAUNCH_SPANS = ("repro.fl.flush.launch", "repro.serve.prefill")
WAIT_SPANS = ("repro.fl.flush.wait", "repro.serve.first_token")
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
_bench_summarize = tr.summarize
_JIT_PART = re.compile(r"^p?jit\(.*\)$")
_NO_ARGS: Mapping[str, Any] = MappingProxyType({})


class Event(NamedTuple):
    """``bench.trace.Event`` with a host span's arguments and a device
    op's named-scope path (its ``op_name`` less the ``jit(...)`` parts)."""
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""
    args: Mapping[str, Any] = _NO_ARGS
    scope: str = ""


def scope_of(op_name: str) -> str:
    return "/".join(p for p in op_name.split("/") if not _JIT_PART.match(p))


def instruction_of(op_event_name: str) -> str:
    """The HLO instruction an op event names: ``%fusion.3 = f32[...] ...``
    on a TPU, ``fusion.3`` on the host."""
    return op_event_name.split(" = ", 1)[0].lstrip("%")


# -- the HLO of each program, from the trace's metadata plane --------------
def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, Any]]:
    """(field number, value) of one serialized protobuf message: an int
    for a varint, a memoryview for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} is not supported")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _instruction_op_names(hlo_proto) -> Dict[str, str]:
    """``{instruction name: op_name}`` of a serialized ``xla.HloProto``
    (``hlo_module`` 1; ``computations`` 3; ``instructions`` 2; an
    instruction's ``name`` 1 and ``metadata`` 7, whose ``op_name`` is 2)."""
    out: Dict[str, str] = {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for f, comp in _fields(module):
            if f != 3:
                continue
            for f, ins in _fields(comp):
                if f != 2:
                    continue
                name = op_name = ""
                for f, v in _fields(ins):
                    if f == 1:
                        name = _text(v)
                    elif f == 7:
                        for g, w in _fields(v):
                            if g == 2:
                                op_name = _text(w)
                if name and op_name:
                    out[name] = op_name
    return out


def hlo_op_names(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: op_name}}`` from the HLO protos of a
    serialized ``XSpace``'s metadata plane: its event metadata are named
    after the programs (``jit_f(<id>)``) and hold ``Hlo Proto`` stats
    (``XSpace.planes`` 1; ``XPlane.name`` 2, ``event_metadata`` 4 and
    ``stat_metadata`` 5, maps whose entries hold the value at 2;
    ``XEventMetadata.name`` 2 and ``stats`` 5; ``XStat.metadata_id`` 1 and
    ``bytes_value`` 6; ``XStatMetadata.name`` 2)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xplane)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif name != METADATA_PLANE:
                break               # fields come in order: the name first
            elif g == 4:
                events += [w for h, w in _fields(v) if h == 2]
            elif g == 5:
                for h, w in _fields(v):
                    if h == 2:
                        meta = dict(_fields(w))
                        stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if name != METADATA_PLANE:
            continue
        for ev in events:
            program, protos = "", []
            for g, v in _fields(ev):
                if g == 2:
                    program = _text(v)
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT and 6 in stat:
                        protos.append(stat[6])
            for proto in protos:
                out.setdefault(program, {}).update(_instruction_op_names(proto))
    return out


def _program_of(modules: List[Tuple[float, float, str]], starts: List[float],
                t: float) -> str:
    """The program of ``modules`` (sorted ``(start, end, name)``, ``starts``
    their starts) whose run holds the time ``t``, or ``""``."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][2] if i >= 0 and t < modules[i][1] else ""


def load_events(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir`` that
    ``bench.trace.load_xplane`` keeps (the device planes' events and the
    host's ``bench.*`` spans, alike), the host's ``repro.*`` spans with
    their arguments, and the scope of each device op."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    op_names = hlo_op_names(raw)
    by_program: Dict[str, Dict[str, str]] = {}
    for program, names in op_names.items():
        by_program.setdefault(tr.program_name(program), {}).update(names)
    scopes: Dict[Tuple[str, str], str] = {}     # (program, op) -> scope
    out: List[Event] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        device = bool(tr.DEVICE_PLANE.match(plane.name))
        events: List[Tuple[str, Any]] = []
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name.startswith(HOST_PREFIXES):
                    events.append((line.name, ev))
        modules = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                          ev.name) for line, ev in events
                         if line == "XLA Modules")
        starts = [m[0] for m in modules]
        for line, ev in events:
            module, scope, args = "", "", _NO_ARGS
            if not device:
                args = MappingProxyType({k: v for k, v in ev.stats})
            elif line == "XLA Ops":
                for k, v in ev.stats:
                    if k == "hlo_module":
                        module = str(v)
                program = _program_of(modules, starts, float(ev.start_ns))
                key = (program or module, ev.name)
                scope = scopes.get(key)
                if scope is None:
                    names = op_names.get(program) or by_program.get(
                        tr.program_name(program or module), {})
                    scope = scopes[key] = scope_of(
                        names.get(instruction_of(ev.name), ""))
            out.append(Event(plane.name, line, ev.name, float(ev.start_ns),
                             float(ev.duration_ns), module, args, scope))
    return out


def window_of(events: List[Event]) -> Tuple[float, float]:
    """The window ``bench.trace.summarize`` reduces: the ``bench.window``
    span, else the device events' extent."""
    win = [e for e in events if e.name == tr.WINDOW_SPAN
           and not tr.DEVICE_PLANE.match(e.plane)]
    if not win:
        win = [e for e in events if tr.DEVICE_PLANE.match(e.plane)]
    return (min(e.start_ns for e in win),
            max(e.start_ns + e.dur_ns for e in win))


def _modules(events: List[Event], plane: str) -> Dict[str, List[Event]]:
    by: Dict[str, List[Event]] = collections.defaultdict(list)
    for e in events:
        if e.plane == plane and e.line == "XLA Modules":
            by[tr.program_name(e.name)].append(e)
    for evs in by.values():
        evs.sort(key=lambda e: e.start_ns)
    return by


def clock_offset(events: List[Event], plane: str) -> Tuple[
        float, Optional[float], Optional[float], int]:
    """(offset, lower bound, upper bound, pairs) of the device clock of
    ``plane`` less the host clock, in ns (see the module's docstring)."""
    modules = _modules(events, plane)
    lo, hi, pairs = -math.inf, math.inf, 0
    for names, launch in ((LAUNCH_SPANS, True), (WAIT_SPANS, False)):
        by: Dict[str, List[Event]] = collections.defaultdict(list)
        for e in events:
            if e.name in names and "program" in e.args:
                by[str(e.args["program"])].append(e)
        for program, spans in by.items():
            spans.sort(key=lambda e: e.start_ns)
            for s, d in zip(spans, modules.get(program, [])):
                pairs += 1
                if launch:      # the device starts after the launch began
                    hi = min(hi, d.start_ns - s.start_ns)
                else:           # and ends before the wait ended
                    lo = max(lo, d.start_ns + d.dur_ns
                             - (s.start_ns + s.dur_ns))
    bounds = (None if lo == -math.inf else lo, None if hi == math.inf else hi)
    if lo > hi:
        return 0.0, bounds[0], bounds[1], pairs
    if math.isinf(lo) or math.isinf(hi):
        return min(max(0.0, lo), hi), bounds[0], bounds[1], pairs
    return (lo + hi) / 2, bounds[0], bounds[1], pairs


def span_table(events: List[Event], lo: float, hi: float) -> Dict[str, Dict]:
    """Count, seconds, self seconds and summed numeric arguments of each
    ``repro.*`` span, clipped to the window ``[lo, hi)``."""
    table: Dict[str, Dict] = {}
    by_thread: Dict[Tuple[str, str], List[Event]] = collections.defaultdict(list)
    for e in events:
        if e.name.startswith(PROGRAM_PREFIX) and \
                tr._clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi):
            by_thread[(e.plane, e.line)].append(e)
    for spans in by_thread.values():
        spans.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: List[Tuple[Event, Dict]] = []
        for e in spans:
            s, t = tr._clip(e.start_ns, e.start_ns + e.dur_ns, lo, hi)
            while stack and stack[-1][0].start_ns + stack[-1][0].dur_ns <= e.start_ns:
                stack.pop()
            row = table.setdefault(e.name, {"count": 0, "seconds": 0.0,
                                            "self_s": 0.0, "args": {}})
            row["count"] += 1
            row["seconds"] += (t - s) * 1e-9
            row["self_s"] += (t - s) * 1e-9
            if stack:
                stack[-1][1]["self_s"] -= (t - s) * 1e-9
            for k, v in e.args.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    row["args"][k] = row["args"].get(k, 0) + v
            stack.append((e, row))
    return table


def scope_seconds(events: List[Event], lo: float, hi: float,
                  offset: float = 0.0) -> Dict[str, Dict[str, float]]:
    """Device seconds of each program's ops by top-level named scope, the
    union of their intervals inside the window, averaged over the device
    planes.  An op whose event names no module belongs to the program
    whose ``XLA Modules`` event holds its start."""
    planes = sorted({e.plane for e in events if tr.DEVICE_PLANE.match(e.plane)})
    iv: Dict[Tuple[str, str], List[Tuple[float, float]]] = \
        collections.defaultdict(list)
    for plane in planes:
        mods = sorted((e.start_ns, e.start_ns + e.dur_ns, tr.program_name(e.name))
                      for e in events if e.plane == plane
                      and e.line == "XLA Modules")
        starts = [m[0] for m in mods]
        for e in events:
            if e.plane != plane or e.line != "XLA Ops":
                continue
            program = (tr.program_name(e.module) if e.module
                       else _program_of(mods, starts, e.start_ns))
            c = tr._clip(e.start_ns - offset, e.start_ns + e.dur_ns - offset,
                         lo, hi)
            if c:
                iv[(program, e.scope.split("/")[0])].append(c)
    out: Dict[str, Dict[str, float]] = collections.defaultdict(dict)
    for (program, scope), ivs in iv.items():
        out[program][scope] = sum(
            e - s for s, e in tr._union(ivs)) / len(planes) * 1e-9
    return dict(out)


def idle_self(events: List[Event], plane: str, lo: float, hi: float,
              offset: float) -> Dict[str, float]:
    """Seconds of idle time of ``plane`` in ``[lo, hi)`` by the innermost
    host span covering each piece (see the module's docstring)."""
    ops = [e for e in events if e.plane == plane and e.line == "XLA Ops"] or \
        [e for e in events if e.plane == plane and e.line == "XLA Modules"]
    busy = tr._union([c for c in (tr._clip(e.start_ns - offset,
                                           e.start_ns + e.dur_ns - offset, lo, hi)
                                  for e in ops) if c])
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    spans = [e for e in events if not tr.DEVICE_PLANE.match(e.plane)
             and e.name.startswith(HOST_PREFIXES) and e.name != tr.WINDOW_SPAN]
    # (time, 0 = end / 1 = start, span): ends before starts at one time
    points = sorted([(s.start_ns + s.dur_ns, 0, i) for i, s in enumerate(spans)]
                    + [(s.start_ns, 1, i) for i, s in enumerate(spans)])
    active: set = set()
    out: Dict[str, float] = collections.defaultdict(float)

    def innermost() -> str:
        if not active:
            return "host"
        i = min(active, key=lambda i: (spans[i].dur_ns, -spans[i].start_ns))
        return spans[i].name

    j = 0
    for gs, ge in gaps:
        while j < len(points) and points[j][0] <= gs:
            (active.add if points[j][1] else active.discard)(points[j][2])
            j += 1
        t = gs
        while True:
            nxt = points[j][0] if j < len(points) else math.inf
            end = min(nxt, ge)
            if end > t:
                out[innermost()] += (end - t) * 1e-9
                t = end
            if nxt >= ge:
                break
            (active.add if points[j][1] else active.discard)(points[j][2])
            j += 1
    return dict(out)


def summarize(events: Iterable[Event], top: int = 10) -> Dict:
    """``bench.trace.summarize`` of ``events`` with the keys of the
    program's spans and scopes (see the module's docstring)."""
    events = list(events)
    out = _bench_summarize(events, top)
    lo, hi = window_of(events)
    plane = sorted({e.plane for e in events if tr.DEVICE_PLANE.match(e.plane)})[0]
    offset, off_lo, off_hi, _ = clock_offset(events, plane)
    out["clock_offset_ns"] = offset
    out["clock_offset_interval_ns"] = [off_lo, off_hi]
    out["spans"] = span_table(events, lo, hi)
    out["scopes"] = scope_seconds(events, lo, hi, offset)
    out["breakdown"]["idle_self"] = sorted(
        ([k, v] for k, v in idle_self(events, plane, lo, hi, offset).items()),
        key=lambda kv: -kv[1])
    return out
