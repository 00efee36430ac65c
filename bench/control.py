"""Readings from which a cell's correctness limits are set: over a list of
seeds, in one process, each run's compared numbers (the lower readings)
and those of the control, the reference in the next lower precision put
in the program's place (the upper readings), with any planted fault the
driver reads beside them; and whether the cell's limits, through the
harness's own ``checks_ok``, judge the program and the control correct
(``correct``, ``control_correct``).

    python bench/control.py --workload <cell> --seeds 101 102 103 \\
        --seconds 10 [--out readings.json]

A short window at the cell's own load and sizes precedes each reading.
It runs on the chip only, like the benchmark: without a TPU it exits
with code 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(name: str, seeds, seconds: float, root: str = ROOT,
             devices=None):
    from bench import harness
    from bench.run import enable_compile_cache, find_chips
    spec = harness.load_cell(name, root)
    if devices is None:
        if find_chips(spec["entry"]["chips"]) is None:
            return None
        enable_compile_cache()
    driver = harness.driver_for(spec)
    out = []
    for seed in seeds:
        probe = harness.Probe()
        st = driver.setup(spec, seed, probe)
        driver.window(st, seconds, probe)
        probe.restore()
        r = dict(driver.readings(st, control=True), seed=seed)
        limits = spec["cell"]["limits"]
        r["correct"] = harness.checks_ok(harness.checks_of(r, limits))
        r["control_correct"] = harness.checks_ok(
            harness.checks_of(r, limits, "control_"))
        print(json.dumps(r), flush=True)
        out.append(r)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    out = readings(a.workload, a.seeds, a.seconds)
    if out is None:
        return 2
    if a.out:
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
