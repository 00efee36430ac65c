"""Roofline share of the batched decode program: the least time a step
could take (the larger of its bytes over HBM bandwidth and its FLOPs over
the bf16 peak) over its measured device time per step, in percent.  The
bytes are every weight once plus the keys and values of each active
request's context; the FLOPs those of its generated tokens."""
from bench import flops
from bench.trace import program_seconds

DECODE = r"^jit_step$"


def read(ctx):
    c, hf = ctx["counters"], ctx["config"]
    steps = c.get("decode_steps")
    secs, launches = program_seconds(ctx["trace"], DECODE)
    if not steps or not launches:
        return None
    per_step = secs / launches
    step_bytes = (flops.lm_params(hf) * 2
                  + flops.lm_kv_bytes_per_position(hf) * c["decode_ctx_positions"]
                  / steps)
    step_flops = flops.lm_served_flops(hf, {
        "requests": 0, "prompt_tokens": 0, "prompt_pairs": 0,
        "decode_tokens": c["decode_tokens"],
        "decode_ctx_positions": c["decode_ctx_positions"]}) / steps
    least = max(step_bytes / ctx["peaks"]["hbm_bytes_per_s"],
                step_flops / ctx["peaks"]["bf16_flops"])
    return least / per_step * 100
