"""p99 over all requests offered in the window, each timed from its due
time, a failed request above every limit.  On a one-chip host it swings
with host stalls of 20-100 ms that come in some runs and not others, so it
is read here, beside the steadier end-to-end p95."""
from bench import stats


def read(ctx):
    c = ctx["counters"]
    lat = c.get("due_latency_ms")
    if lat is None or not len(lat) + c["n_failed"]:
        return None
    return stats.tail(lat, 0.99, c["n_failed"])
