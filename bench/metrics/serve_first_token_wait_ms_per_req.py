"""Host time admission waits for a request's first token per admitted
request: the program's ``repro.serve.first_token`` spans around the
argmax sync that follows the prefill."""


def read(ctx):
    admitted = ctx["counters"].get("admitted")
    span = ctx["trace"].get("spans", {}).get("repro.serve.first_token")
    if not admitted or not span:
        return None
    return span["seconds"] / admitted * 1e3
