"""Megabytes copied between host and device per folded update, as the
program counts them in its spans' ``nbytes``: a flush's inputs to the
device (``stage``), its result to the host (``copy``), the host-resident
updates each fold moves to the device (``aggregate``) and the test images
of each evaluation (``evaluate``)."""

SPANS = ("repro.fl.flush.stage", "repro.fl.flush.copy", "repro.fl.aggregate",
         "repro.fl.evaluate")


def read(ctx):
    updates = ctx["counters"].get("updates")
    spans = ctx["trace"].get("spans", {})
    if not updates or "repro.fl.flush.copy" not in spans:
        return None
    nbytes = sum(spans[s]["args"].get("nbytes", 0) for s in SPANS if s in spans)
    return nbytes / updates / 1e6
