"""p99 of the server's own latency (``AssignResponse.latency_ms``: submit
to scatter) over the requests answered in the window."""
from bench import stats


def read(ctx):
    lat = ctx["counters"].get("server_latency_ms")
    return stats.tail(lat, 0.99) if lat is not None and len(lat) else None
