"""A traced run of one cell that also reads the program's own spans and
scopes.

    python3 bench/trace_report.py --workload <cell> --seed <n> [--seconds 10] [--keep events.json]

It is ``bench/run.py --trace 1`` with the reduction of
``bench/program_trace.py`` in place of ``bench/trace.py``'s, and with the
per-layer metrics of ``PROGRAM_METRICS`` besides the cell's own: the
result line is ``bench/run.py``'s, its ``breakdown`` gains ``idle_self``
and its metrics gain those that read the program's spans and scopes.  The
clock offset between the host's spans and the device's ops goes to
standard error.  ``--keep`` writes the trace's events, as the reduction
keeps them, to a JSON file.

Without a TPU it exits with code 2, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# the per-layer metrics that read the program's spans and scopes, as
# BENCHMARK.json would list them once bench/run.py reduces its traces
# with bench/program_trace.py
PROGRAM_METRICS = [
    {"name": "fl_flush_stage_ms_per_update", "unit": "ms",
     "workloads": ["cnn-teasq-paper-c1"]},
    {"name": "fl_flush_copy_ms_per_update", "unit": "ms",
     "workloads": ["cnn-teasq-paper-c1"]},
    {"name": "fl_codec_device_ms_per_update", "unit": "ms",
     "workloads": ["cnn-teasq-paper-c1"]},
    {"name": "fl_host_copy_mb_per_update", "unit": "MB",
     "workloads": ["cnn-teasq-paper-c1"]},
    {"name": "serve_first_token_wait_ms_per_req", "unit": "ms",
     "workloads": ["qwen3-serve-chat"]},
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep", default=None, metavar="PATH")
    a = ap.parse_args(argv)
    t0 = time.perf_counter()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, program_trace as pt, run, trace as tr

    def load(trace_dir):
        events = pt.load_events(trace_dir)
        if a.keep:
            with open(a.keep, "w") as f:
                json.dump([list(e[:6]) + [dict(e.args), e.scope]
                           for e in events], f)
        return events

    def summarize(events, top=10):
        s = pt.summarize(events, top)
        lo, hi = s["clock_offset_interval_ns"]
        run.log(f"trace_report: clock offset {s['clock_offset_ns'] / 1e6} ms "
                f"(device less host), feasible interval "
                f"[{lo if lo is None else lo / 1e6}, "
                f"{hi if hi is None else hi / 1e6}] ms")
        return s

    load_cell = harness.load_cell

    def with_program_metrics(name, root=harness.ROOT):
        spec = load_cell(name, root)
        spec["per_layer"] = spec["per_layer"] + [
            m for m in PROGRAM_METRICS if name in m["workloads"]]
        return spec

    tr.load_xplane, tr.summarize = load, summarize
    harness.load_cell = with_program_metrics
    devices = run.find_chips(harness.load_cell(a.workload)["entry"]["chips"])
    if devices is None:
        return 2
    run.log(f"trace_report: compile cache {run.enable_compile_cache()}")
    return run.run_cell(a.workload, a.seed, a.seconds, True,
                        devices=devices, t0=t0)


if __name__ == "__main__":
    sys.exit(main())
