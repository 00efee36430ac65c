"""Tables 3-6 + Fig. 7: FedAvg vs TEA-Fed vs TEAStatic-Fed vs TEASQ-Fed —
highest accuracy within time budgets and time to target accuracy, IID and
non-IID."""
from benchmarks.common import (Scale, best_acc_within, compression_points,
                               print_csv, record, simulate, std_argparser,
                               time_to_acc)
from repro.launch.cache import enable_compile_cache

BUDGET_FRACS = [1 / 6, 1 / 3, 1 / 2, 2 / 3, 5 / 6, 1.0]


def run(scale: Scale):
    rows = []
    for iid in (True, False):
        pts = compression_points(scale, iid=iid)
        sch = pts["schedule"]
        static = dict(p_s=pts["static"][0], p_q=pts["static"][1])
        rows.append(simulate(scale, "fedavg", iid=iid))
        rows.append(simulate(scale, "tea", iid=iid))
        r = simulate(scale, "teastatic", iid=iid, **static)
        r["kw"].update(static)
        rows.append(r)
        r = simulate(scale, "teasq", iid=iid, schedule=sch, **static)
        r["kw"]["schedule"] = f"decay(s0={sch.p_s0_idx},q0={sch.p_q0_idx})"
        rows.append(r)
    # derive table cells
    for r in rows:
        hist = [type("H", (), dict(time=h[0], accuracy=h[2]))()
                for h in r["history"]]
        b = scale.budget_for(r["iid"])
        r["acc_at_budget"] = {f"{f:.2f}": best_acc_within(hist, f * b)
                              for f in BUDGET_FRACS}
        final = max(h[2] for h in r["history"])
        r["time_to_80pct_final"] = time_to_acc(hist, 0.8 * final)
    record("table3_6_compression", rows)
    return rows


def main():
    args = std_argparser(__doc__).parse_args()
    enable_compile_cache()
    rows = run(Scale(args.full))
    print_csv("table3_6", rows)
    for r in rows:
        tag = ("iid" if r["iid"] else "noniid")
        cells = " ".join(f"{k}:{v:.3f}" for k, v in r["acc_at_budget"].items())
        print(f"# {r['method']}_{tag} acc@budget {cells} "
              f"t80={r['time_to_80pct_final']}")


if __name__ == "__main__":
    main()
