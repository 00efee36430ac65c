"""The benchmark's machinery that no cell owns: finding a cell's files by
name, spans and counters around the program's layers, counting compiles,
loading per-layer metric readers, and the result line.

A cell is named in ``BENCHMARK.json``; everything else is found by name:

* ``bench/workloads/<cell>.json``  — the driver, its settings, the limits
  of the correctness comparison;
* ``bench/configs/<config>.json``  — the configuration as it is run;
* ``bench/traffic/mixes/<traffic>.json`` — the traffic mix's parameters;
* ``bench/drivers/<driver>.py``    — what the window drives;
* ``bench/metrics/<metric>.py``    — one reader per per-layer metric,
  ``read(ctx) -> float | None``.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- finding a cell's files ------------------------------------------------
def _read_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def bench_file(root: str, *parts: str) -> str:
    """``<root>/bench/<parts>``."""
    return os.path.join(root, "bench", *parts)


def load_cell(name: str, root: str = ROOT) -> Dict:
    """Everything one cell needs, merged: its ``BENCHMARK.json`` entry
    (``entry``), its workload file (``cell``), configuration (``config``),
    traffic mix (``mix``) and the metrics it reports (``end_to_end``,
    ``per_layer``: the entries of ``BENCHMARK.json`` that apply to it)."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[entry["config"]]["file"]))
    cell = _read_json(bench_file(root, "workloads", f"{name}.json"))
    mix = _read_json(bench_file(root, "traffic", "mixes",
                                f"{entry['traffic']}.json"))
    applies = lambda m: name in m.get("workloads", [name])
    return {"name": name, "entry": entry, "cell": cell, "config": config,
            "mix": mix, "root": root,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_for(spec: Dict):
    name = spec["cell"]["driver"]
    return load_module(bench_file(spec["root"], "drivers", f"{name}.py"),
                       f"bench_driver_{name}")


def read_metric(spec: Dict, name: str, ctx: Dict) -> Optional[float]:
    mod = load_module(bench_file(spec["root"], "metrics", f"{name}.py"),
                      "bench_metric_" + name.replace(".", "_"))
    value = mod.read(ctx)
    if value is None:
        return None
    value = float(value)
    return value if math.isfinite(value) else None


# -- spans and counters ----------------------------------------------------
class Probe:
    """Host spans and counters around calls into the program's layers.

    ``wrap(owner, attr, span)`` replaces ``owner.attr`` with a function
    that records the call's host interval under ``span`` (and, while
    ``annotate`` is set, writes it into the profiler's trace as
    ``bench.<span>``) and calls the original.  ``restore`` undoes every
    wrap.  Spans and counters are only kept while ``recording``."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.recording = False
        self.spans: Dict[str, List[Tuple[float, float]]] = {}
        self.counters: Dict[str, float] = {}
        self._undo: List[Callable[[], None]] = []

    def count(self, name: str, n: float = 1) -> None:
        if self.recording:
            self.counters[name] = self.counters.get(name, 0) + n

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.recording:
            yield
            return
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.setdefault(name, []).append((t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def wrap(self, owner: Any, attr: str, span: str,
             after: Optional[Callable] = None) -> None:
        inner = getattr(owner, attr)
        had_own = attr in vars(owner) if hasattr(owner, "__dict__") else True

        def wrapped(*args, **kwargs):
            with self.span(span):
                out = inner(*args, **kwargs)
            if after is not None:
                after(args, kwargs, out)
            return out

        setattr(owner, attr, wrapped)

        def undo():
            if had_own:
                setattr(owner, attr, inner)
            else:
                delattr(owner, attr)
        self._undo.append(undo)

    def restore(self) -> None:
        while self._undo:
            self._undo.pop()()

    def total(self, name: str) -> float:
        return sum(e - s for s, e in self.spans.get(name, []))


class CompileCounter:
    """Counts, while armed, the executables JAX builds or loads: every
    ``backend_compile`` event (in JAX 0.9 it spans
    ``compile_or_get_cached``, so a load from the persistent cache is one
    too) and, apart, the loads from the persistent cache.  Inside the
    measured window there should be none of either."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.cache_loads = 0

        def on_event(event, duration, **kwargs):
            if not self.armed:
                return
            if event == self.COMPILE:
                self.count += 1
            elif event == self.CACHE_LOAD:
                self.cache_loads += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)


# -- the result --------------------------------------------------------------
def checks_of(readings: Dict[str, float], limits: Dict[str, float],
              prefix: str = "") -> List[Tuple[str, float, float]]:
    """The compared numbers of ``readings`` beside their limits: the
    program's, or with ``prefix`` (``"control_"``) the control's in the
    program's place."""
    return [(k, readings.get(prefix + k, float("nan")), lim)
            for k, lim in limits.items()]


def checks_ok(checks: List[Tuple[str, float, float]]) -> bool:
    return bool(checks) and all(
        isinstance(v, float) and math.isfinite(v) and v <= lim
        for _, v, lim in checks)


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Tuple[float, str]], device: Dict,
                checks: List[Tuple[str, float, float]],
                breakdown: Optional[Dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": u} for k, (v, u) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return json.dumps(out)
