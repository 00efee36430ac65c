"""Benchmark entry point: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run`` runs the quick-scale suite and
prints ``name,us_per_call,derived`` CSV.  ``--full`` uses the paper's scale;
``--only fig2,table7`` selects subsets.  Roofline rows are appended from the
dry-run JSONs if present (run repro.launch.dryrun first for those).
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import (engine_scale, fig2_mu, fig3_c_fraction, fig6_alpha,
                        fig8_ablation, fig9_sota, table3_6_compression,
                        table7_sizes)
from benchmarks.common import Scale, print_csv
from repro.launch.cache import enable_compile_cache

SUITES = {
    "fig2": (fig2_mu, "fig2_mu"),
    "fig3_5": (fig3_c_fraction, "fig3_5_c"),
    "fig6": (fig6_alpha, "fig6_alpha"),
    "table3_6": (table3_6_compression, "table3_6"),
    "fig8": (fig8_ablation, "fig8_ablation"),
    "table7": (table7_sizes, "table7"),
    "fig9": (fig9_sota, "fig9_sota"),
    "engine_scale": (engine_scale, "engine_scale"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of " + ",".join(SUITES))
    args = ap.parse_args()
    enable_compile_cache()

    # engine_scale is a wall-clock race at N=1000 — opt-in via --only
    names = [n.strip() for n in args.only.split(",") if n.strip()] or \
        [n for n in SUITES if n != "engine_scale"]
    scale = Scale(args.full)
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in names:
        mod, tag = SUITES[name]
        try:
            rows = mod.run(scale)
            if name == "table7":
                for r in rows:
                    d = "iid" if r["iid"] else "noniid"
                    print(f"table7/{r['method']}_{d},{r['us_per_round']:.1f},"
                          f"max_up_{r['max_up_kb']:.1f}KB")
            elif name == "engine_scale":
                for r in rows:
                    print(f"engine_scale/{r['backend']}_n{r['n_devices']},"
                          f"{r['wall_s'] * 1e6 / max(r['rounds'], 1):.1f},"
                          f"wall={r['wall_s']:.1f}s_rounds={r['rounds']}")
            else:
                print_csv(tag, rows)
        except Exception as e:  # pragma: no cover
            print(f"{tag}/ERROR,0,{e!r}", file=sys.stderr)
            raise
        print(f"# {name} done at {time.time()-t0:.0f}s", file=sys.stderr)

    # roofline rows (from dry-run artifacts, if present)
    try:
        from benchmarks import roofline
        rows = roofline.load(["results/dryrun_single.json"])
        for rec in rows:
            if "error" in rec:
                continue
            r = roofline.analyze(rec, 256, roofline.DRYRUN_DEVICE_KIND)
            if r:
                dom_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
                print(f"roofline/{r['arch']}_{r['shape']},{dom_s*1e6:.1f},"
                      f"dom={r['dominant']}")
    except Exception as e:  # pragma: no cover
        print(f"# roofline skipped: {e!r}", file=sys.stderr)


if __name__ == "__main__":
    main()
