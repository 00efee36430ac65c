"""Device time of admission's programs per admitted request: the one-row
prefill (``jit_prefill``) and the cache extension (``jit_extend_cache``),
from the device trace."""
from bench.trace import program_seconds

PROGRAMS = r"^(jit_prefill|jit_extend_cache)$"


def read(ctx):
    admitted = ctx["counters"].get("admitted")
    secs, launches = program_seconds(ctx["trace"], PROGRAMS)
    if not admitted or not launches:
        return None
    return secs / admitted * 1e3
