"""Fig. 9: comparison with SOTA baselines — PORT, ASO-Fed (async) and MOON
(synchronous, model-contrastive).  See DESIGN.md for the faithful-but-
simplified baseline implementations."""
from benchmarks.common import (Scale, compression_points, print_csv,
                               record, scale_from_args, simulate,
                               std_argparser)
from repro.launch.cache import enable_compile_cache


def run(scale: Scale):
    pts = compression_points(scale, iid=False)
    sch = pts["schedule"]
    p_s, p_q = pts["static"]
    rows = [
        simulate(scale, "teasq", iid=False, p_s=p_s, p_q=p_q, schedule=sch),
        simulate(scale, "port", iid=False, c_fraction=0.3),
        simulate(scale, "asofed", iid=False),
        simulate(scale, "moon", iid=False),
    ]
    record("fig9_sota", rows)
    return rows


def main():
    args = std_argparser(__doc__).parse_args()
    enable_compile_cache()
    print_csv("fig9_sota", run(scale_from_args(args)))


if __name__ == "__main__":
    main()
