"""Figs. 3-5: effect of the C-fraction (accuracy vs time and vs rounds,
time-to-target), IID and non-IID, vs FedAvg / FedAsync baselines."""
from benchmarks.common import (Scale, print_csv, record,
                               scale_from_args, simulate, std_argparser)
from repro.launch.cache import enable_compile_cache

CS = [0.05, 0.1, 0.3]


def run(scale: Scale):
    rows = []
    for iid in (True, False):
        for c in CS:
            r = simulate(scale, "tea", iid=iid, c_fraction=c)
            r["kw"]["c_fraction"] = c
            rows.append(r)
        rows.append(simulate(scale, "fedavg", iid=iid))
        rows.append(simulate(scale, "fedasync", iid=iid))
    record("fig3_5_c_fraction", rows)
    return rows


def main():
    args = std_argparser(__doc__).parse_args()
    enable_compile_cache()
    print_csv("fig3_5_c", run(scale_from_args(args)))


if __name__ == "__main__":
    main()
