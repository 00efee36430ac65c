"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up the cell (set-up, timed as ``setup_s``), measures for
``--seconds``, checks what the measured window produced against a plain
reference, and prints as its last line of standard output one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and,
with ``--trace 1``, ``breakdown``), and last the numbers compared with
their limits under ``checks``.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` profiles the first seconds of the
window (at most ``TRACE_SECONDS``) and reports its per-layer metrics.

Without a TPU, or with fewer chips than the cell asks for, it exits with
code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 10.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """The program's persistent compile cache
    (``repro.launch.cache.enable_compile_cache``: ``<checkout>/.jax_cache``,
    or where ``JAX_COMPILATION_CACHE_DIR`` says), with every program
    cached however short its compile, so that a second run compiles
    nothing: the program's own thresholds leave programs that compile in
    under a second out of the cache."""
    import jax
    from repro.launch.cache import enable_compile_cache as program_cache
    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_chips(chips: int):
    """The devices of a run, or ``None`` where JAX finds no TPU or fewer
    than ``chips``."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"run: JAX found no devices: {e}")
        return None
    if devices[0].platform != "tpu":
        log(f"run: no TPU (JAX found {devices[0].platform}); nothing was run")
        return None
    if len(devices) < chips:
        log(f"run: the cell needs {chips} TPUs, JAX found {len(devices)}")
        return None
    return devices[:chips]


class GcPauses:
    """Python's garbage collections while armed: how many, the longest and
    the total, in seconds."""

    def __init__(self):
        self.armed = False
        self.n, self.longest, self.total, self._t = 0, 0.0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d, self._t = time.perf_counter() - self._t, None
            if self.armed:
                self.n += 1
                self.total += d
                self.longest = max(self.longest, d)

    def close(self):
        gc.callbacks.remove(self._on)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, devices=None, t0: float = None) -> int:
    """One run of cell ``name``; prints the result line.  ``devices`` are
    those ``find_chips`` returned (the tests pass the CPU's); ``t0`` is
    when the process started its set-up."""
    t_setup = time.perf_counter() if t0 is None else t0
    import jax
    from bench import harness, trace as tr
    from bench.peaks import peaks_for

    spec = harness.load_cell(name, root)
    dev0 = devices[0]
    driver = harness.driver_for(spec)
    compiles = harness.CompileCounter()
    pauses = GcPauses()
    probe = harness.Probe(annotate=trace)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        state = driver.setup(spec, seed, probe)
        setup_s = time.perf_counter() - t_setup
        log(f"run: {name} seed {seed} set up in {setup_s:.3f} s")
        window_s = min(seconds, TRACE_SECONDS) if trace else float(seconds)
        compiles.armed = pauses.armed = True
        probe.recording = trace
        if trace:
            # host spans and device ops only: tracing every Python call
            # slows the window and makes the trace slow to read
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with probe.span("window"):
                out = driver.window(state, window_s, probe)
        finally:
            if trace:
                jax.profiler.stop_trace()
            compiles.armed = pauses.armed = False
            probe.recording = False
    finally:
        probe.restore()
        pauses.close()
    log(f"run: compiles inside the window: {compiles.count} "
        f"({compiles.cache_loads} of them loads from the persistent cache); "
        f"garbage collections: {pauses.n}, longest {pauses.longest * 1e3:.3f} "
        f"ms, {pauses.total * 1e3:.3f} ms in all")
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak(devices),
              "window_compiles": compiles.count,
              "window_cache_loads": compiles.cache_loads}

    checks = driver.check(state)
    del state
    gc.collect()
    correct = harness.checks_ok(checks)

    metrics, breakdown = {}, None
    if not trace:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = (values[m["name"]], m["unit"])
    else:
        t_read = time.perf_counter()
        try:
            summary = tr.summarize(tr.load_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"run: trace read in {time.perf_counter() - t_read:.3f} s")
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = summary["breakdown"]
        ctx = {"spans": probe.spans, "counters": probe.counters,
               "trace": summary, "window_s": out["window_s"],
               "peaks": peaks_for(dev0.device_kind), "spec": spec,
               "config": spec["config"]}
        for m in spec["per_layer"]:
            v = harness.read_metric(spec, m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = (v, m["unit"])
    for n, v, lim in checks:
        log(f"check {n} {v!r} limit {lim!r}")
    print(harness.result_line(correct, out["attempted"], out["failed"],
                              metrics, device, checks, breakdown), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if a.seed < 0 or a.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    t0 = time.perf_counter()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    chips = harness.load_cell(a.workload)["entry"]["chips"]
    devices = find_chips(chips)
    if devices is None:
        return 2
    log(f"run: platform {devices[0].platform}, device_kind "
        f"{devices[0].device_kind}, {len(devices)} device(s); compile cache "
        f"{enable_compile_cache()}")
    return run_cell(a.workload, a.seed, a.seconds, bool(a.trace),
                    devices=devices, t0=t0)


if __name__ == "__main__":
    sys.exit(main())
