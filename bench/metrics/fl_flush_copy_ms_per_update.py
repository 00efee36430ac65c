"""Host time of the cohort round's device-to-host copy per folded update:
the program's ``repro.fl.flush.copy`` spans, which begin once the round
has finished on the device (``repro.fl.flush.wait``)."""


def read(ctx):
    updates = ctx["counters"].get("updates")
    span = ctx["trace"].get("spans", {}).get("repro.fl.flush.copy")
    if not updates or not span:
        return None
    return span["seconds"] / updates * 1e3
