"""Host time of admission per admitted request: the spans around the
batcher's ``_admit`` (prefill, cache extension, the argmax sync and the
splice into the batched cache), over the requests admitted."""


def read(ctx):
    admitted = ctx["counters"].get("admitted")
    spans = ctx["spans"].get("admit", [])
    if not admitted or not spans:
        return None
    return sum(e - s for s, e in spans) / admitted * 1e3
