"""Table 7: maximum transmitted model size per method (wire bytes)."""
from benchmarks.common import (Scale, compression_points, record,
                               simulate, std_argparser)
from repro.launch.cache import enable_compile_cache


def run(scale: Scale):
    rows = []
    for iid in (True, False):
        pts = compression_points(scale, iid=iid)
        sch = pts["schedule"]
        p_s, p_q = pts["static"]
        short = dict(time_budget=scale.budget_for(iid) / 3)
        for method, kw in [("fedavg", {}), ("tea", {}),
                           ("teastatic", dict(p_s=p_s, p_q=p_q)),
                           ("teasq", dict(p_s=p_s, p_q=p_q, schedule=sch))]:
            r = simulate(scale, method, iid=iid, **short, **kw)
            h = r["history"][-1]
            r["max_up_kb"] = h[5] / 1024
            r["max_down_kb"] = h[6] / 1024
            rows.append(r)
    record("table7_sizes", rows)
    return rows


def main():
    args = std_argparser(__doc__).parse_args()
    enable_compile_cache()
    rows = run(Scale(args.full))
    for r in rows:
        tag = "iid" if r["iid"] else "noniid"
        print(f"table7/{r['method']}_{tag},{r['us_per_round']:.1f},"
              f"up={r['max_up_kb']:.1f}KB down={r['max_down_kb']:.1f}KB")


if __name__ == "__main__":
    main()
