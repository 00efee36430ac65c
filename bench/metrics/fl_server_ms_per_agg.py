"""Host time of one Eqs. 6-10 aggregation: the mean span around the
server's aggregate call, which returns once the fold is dispatched."""


def read(ctx):
    spans = ctx["spans"].get("aggregate", [])
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) * 1e3
