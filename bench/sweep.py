"""Find the highest arrival rate a serving cell sustains, with the cell's
own driver, and write 0.8 of it into the cell's traffic mix.

    python bench/sweep.py --workload <cell> --rates 1 2 3 4 --seconds 20 \\
        --seed 7 [--write]

One process sets the cell up once per rate (the compiled programs are
shared) and runs the window at each rate in turn.  A rate is sustained
when, at the window's close, no more than ``--max-queue`` requests wait
unadmitted and the p95 time to first token is under ``--max-ttft-ms``:
above the knee the queue grows all through the window and neither holds.
``--write`` sets the mix's ``arrivals.rate`` to ``--share`` (0.8) of the
highest sustained rate and records the sweep beside it under ``sweep``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def sweep(name: str, rates, seconds: float, seed: int, max_queue: int,
          max_ttft_ms: float, root: str = ROOT):
    from bench import harness
    from bench.run import enable_compile_cache, find_chips
    spec = harness.load_cell(name, root)
    if find_chips(spec["entry"]["chips"]) is None:
        return None
    enable_compile_cache()
    driver = harness.driver_for(spec)
    rows = []
    for rate in rates:
        spec["mix"]["arrivals"]["rate"] = float(rate)
        probe = harness.Probe()
        st = driver.setup(spec, seed, probe)
        out = driver.window(st, seconds, probe)
        probe.restore()
        e2e = out["end_to_end"]
        row = {"rate": rate, "queue_at_close": st.queue_at_close,
               "drain_s": out["window_s"] - seconds, **e2e}
        row["sustained"] = (row["queue_at_close"] <= max_queue
                            and e2e["ttft_p95_ms"] <= max_ttft_ms)
        print(json.dumps(row), flush=True)
        rows.append(row)
        st.cb = None
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--max-queue", type=int, default=2)
    ap.add_argument("--max-ttft-ms", type=float, default=2000.0)
    ap.add_argument("--share", type=float, default=0.8)
    ap.add_argument("--write", action="store_true")
    a = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    rows = sweep(a.workload, a.rates, a.seconds, a.seed, a.max_queue,
                 a.max_ttft_ms)
    if rows is None:
        return 2
    ok = [r["rate"] for r in rows if r["sustained"]]
    knee = max(ok) if ok else None
    print(json.dumps({"knee": knee}), flush=True)
    if a.write and knee is not None:
        from bench import harness
        entry = harness.load_cell(a.workload)["entry"]
        path = os.path.join(ROOT, "bench", "traffic", "mixes",
                            f"{entry['traffic']}.json")
        with open(path) as f:
            mix = json.load(f)
        mix["arrivals"]["rate"] = round(a.share * knee, 3)
        mix["sweep"] = {"knee": knee, "share": a.share, "seconds": a.seconds,
                        "seed": a.seed, "at": time.strftime("%Y-%m-%d"),
                        "rows": rows}
        with open(path, "w") as f:
            json.dump(mix, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
