"""Model FLOP utilization of serving a latent-attention MoE model on its
share of the experts: the FLOPs of every prompt and generated token of the
traced run (``bench.flops_mla.served_flops``; the routed experts' from the
program's count of the token-expert pairs its held experts computed) over
its wall time times the chip's bf16 peak, in percent."""
from bench.flops_mla import served_flops

KEYS = ("requests", "prompt_tokens", "prompt_pairs", "decode_tokens",
        "decode_ctx_positions", "held_pairs")


def read(ctx):
    c = ctx["counters"]
    if not all(k in c for k in KEYS):
        return None
    return (served_flops(ctx["config"], c)
            / (ctx["window_s"] * ctx["peaks"]["bf16_flops"]) * 100)
