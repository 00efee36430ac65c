"""Model FLOP utilization of serving: the FLOPs of every prompt and
generated token of the traced run (``bench.flops.lm_served_flops``) over
its wall time times the chip's bf16 peak, in percent."""
from bench.flops import lm_served_flops

KEYS = ("requests", "prompt_tokens", "prompt_pairs", "decode_tokens",
        "decode_ctx_positions")


def read(ctx):
    c = ctx["counters"]
    if not all(k in c for k in KEYS):
        return None
    return (lm_served_flops(ctx["config"], c)
            / (ctx["window_s"] * ctx["peaks"]["bf16_flops"]) * 100)
