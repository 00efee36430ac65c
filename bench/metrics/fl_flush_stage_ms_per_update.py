"""Host time the cohort trainer spends staging a flush's inputs per
folded update: the self time of the program's ``repro.fl.flush.stage``
spans (the version stack, the index arrays and their copies to the
device), from the trace's program spans."""


def read(ctx):
    updates = ctx["counters"].get("updates")
    span = ctx["trace"].get("spans", {}).get("repro.fl.flush.stage")
    if not updates or not span:
        return None
    return span["self_s"] / updates * 1e3
