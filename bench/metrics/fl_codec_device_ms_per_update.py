"""Device time of the in-graph codec per folded update: the ops of the
cohort round program (``jit__cohort_round``) under its ``codec_down`` and
``codec_up`` named scopes, from the device trace."""

PROGRAM = "jit__cohort_round"
SCOPES = ("codec_down", "codec_up")


def read(ctx):
    updates = ctx["counters"].get("updates")
    scopes = ctx["trace"].get("scopes", {}).get(PROGRAM, {})
    if not updates or not any(s in scopes for s in SCOPES):
        return None
    return sum(scopes.get(s, 0.0) for s in SCOPES) / updates * 1e3
