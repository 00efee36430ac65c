"""Device time of the cohort round program (``jit__cohort_round``) per
folded update, from the device trace."""
from bench.trace import program_seconds


def read(ctx):
    updates = ctx["counters"].get("updates")
    secs, launches = program_seconds(ctx["trace"], r"_cohort_round")
    if not updates or not launches:
        return None
    return secs / updates * 1e3
