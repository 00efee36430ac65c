"""Roofline share of the batched decode program (``jit_step``) of a
latent-attention MoE model on its share of the experts: the least time a
step could take over its measured device time per step, in percent.  The
least time is the larger of its bytes over HBM bandwidth (every weight
held here once, the gathered embedding aside, and the cached latent and
rope key of each active request's context) and its FLOPs over the bf16
peak (its generated tokens, with the routed pairs they send to the held
experts under uniform routing)."""
from bench import flops_mla
from bench.trace import program_seconds

DECODE = r"^jit_step$"


def read(ctx):
    c, hf = ctx["counters"], ctx["config"]
    steps = c.get("decode_steps")
    secs, launches = program_seconds(ctx["trace"], DECODE)
    if not steps or not launches or "experts_held" not in hf:
        return None
    tokens = c["decode_tokens"]
    step_bytes = flops_mla.decode_step_bytes(hf, c["decode_ctx_positions"]
                                             / steps)
    step_flops = flops_mla.served_flops(hf, {
        "requests": 0, "prompt_tokens": 0, "prompt_pairs": 0,
        "decode_tokens": tokens,
        "decode_ctx_positions": c["decode_ctx_positions"],
        "held_pairs": flops_mla.expected_held_pairs(hf, tokens)}) / steps
    least = max(step_bytes / ctx["peaks"]["hbm_bytes_per_s"],
                step_flops / ctx["peaks"]["bf16_flops"])
    return least / (secs / launches) * 100
