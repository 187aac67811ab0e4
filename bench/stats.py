"""Order statistics the metrics are taken with."""
from __future__ import annotations

import math


def tail(values, q: float, n_failed: int = 0) -> float:
    """Nearest-rank ``q`` quantile of ``values`` with ``n_failed`` more
    entries that count as above every limit.

    Returns ``math.inf`` where the rank falls among the failed entries: a
    failed request is never clamped to a finite time.
    """
    vals = sorted(float(v) for v in values)
    total = len(vals) + int(n_failed)
    if total == 0:
        raise ValueError("no samples")
    rank = max(1, math.ceil(q * total))
    return vals[rank - 1] if rank <= len(vals) else math.inf


def per_job(window_s: float, jobs: int) -> float:
    """The whole window over the jobs completed in it."""
    if jobs < 1:
        raise ValueError("no job completed in the window")
    return window_s / jobs
