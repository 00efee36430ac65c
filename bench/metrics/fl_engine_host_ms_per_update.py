"""Host time of the event engine per folded update: the traced window's
wall time less the spans around the cohort trainer's flushes, the
server's aggregations and the evaluations, over the updates folded."""


def read(ctx):
    updates = ctx["counters"].get("updates")
    spans = ctx["spans"]
    if not updates or "flush" not in spans:
        return None
    inside = sum(e - s for name in ("flush", "aggregate", "evaluate")
                 for s, e in spans.get(name, []))
    return (ctx["window_s"] - inside) / updates * 1e3
