"""Fig. 8: compression ablation — TEA vs TEAS (sparsification only) vs TEAQ
(quantization only) vs TEASQ (both)."""
from benchmarks.common import (Scale, compression_points, print_csv,
                               record, scale_from_args, simulate,
                               std_argparser)
from repro.launch.cache import enable_compile_cache


def run(scale: Scale):
    p_s, p_q = compression_points(scale, iid=False)["static"]
    rows = [
        simulate(scale, "tea", iid=False),
        simulate(scale, "teas", iid=False, p_s=p_s),
        simulate(scale, "teaq", iid=False, p_q=p_q),
        simulate(scale, "teastatic", iid=False, p_s=p_s, p_q=p_q),
    ]
    record("fig8_ablation", rows)
    return rows


def main():
    args = std_argparser(__doc__).parse_args()
    enable_compile_cache()
    print_csv("fig8_ablation", run(scale_from_args(args)))


if __name__ == "__main__":
    main()
