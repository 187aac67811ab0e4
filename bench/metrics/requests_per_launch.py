"""Requests per coalesced launch in the window (the batcher's counters)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("launches"):
        return None
    return c["requests"] / c["launches"]
