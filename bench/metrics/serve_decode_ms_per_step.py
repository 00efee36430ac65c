"""Device time of the batched decode program (``jit_step``) per launch,
from the device trace."""
from bench.trace import program_seconds

DECODE = r"^jit_step$"


def read(ctx):
    secs, launches = program_seconds(ctx["trace"], DECODE)
    if not launches:
        return None
    return secs / launches * 1e3
