"""Fig. 6: robustness to the mixing hyper-parameter alpha."""
from benchmarks.common import (Scale, print_csv, record,
                               scale_from_args, simulate, std_argparser)
from repro.launch.cache import enable_compile_cache

ALPHAS = [0.2, 0.6, 0.9]


def run(scale: Scale):
    rows = []
    for iid in (True, False):
        for a in ALPHAS:
            r = simulate(scale, "tea", iid=iid, alpha=a)
            r["kw"]["alpha"] = a
            rows.append(r)
    record("fig6_alpha", rows)
    return rows


def main():
    args = std_argparser(__doc__).parse_args()
    enable_compile_cache()
    print_csv("fig6_alpha", run(scale_from_args(args)))


if __name__ == "__main__":
    main()
