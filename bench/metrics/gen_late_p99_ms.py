"""p99 of how late the load generator submitted a request after it was due."""
from bench import stats


def read(ctx):
    late = ctx["counters"].get("gen_late_ms")
    return stats.tail(late, 0.99) if late is not None and len(late) else None
