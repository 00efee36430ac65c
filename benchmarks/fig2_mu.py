"""Fig. 2: effect of the prox regularization weight mu (non-IID)."""
from benchmarks.common import (Scale, print_csv, record,
                               scale_from_args, simulate, std_argparser)
from repro.launch.cache import enable_compile_cache

MUS = [0.0, 0.01, 0.1]


def run(scale: Scale):
    rows = []
    for mu in MUS:
        r = simulate(scale, "tea", iid=False, mu=mu)
        r["kw"]["mu"] = mu
        rows.append(r)
    record("fig2_mu", rows)
    return rows


def main():
    args = std_argparser(__doc__).parse_args()
    enable_compile_cache()
    print_csv("fig2_mu", run(scale_from_args(args)))


if __name__ == "__main__":
    main()
