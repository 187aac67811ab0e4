"""Share of the Lloyd loop's roofline in the device's busy time, in percent.

The work is what the window's jobs required: ``bench.work.chunk_traffic``
of each job's passes (its Lloyd iterations plus two per chunk) at its
``s``, ``n`` and ``k``.  Its roofline time is the larger of its operations
over the chip's peak rate and its bytes over the chip's memory bandwidth
(``bench/peaks.json``); at every configuration here the bytes bound it
(arithmetic intensity about ``k`` operations per byte).  The share divides
that time by the busy seconds of all chips used, whatever ran in them.
"""
from bench import work


def read(ctx):
    red, c = ctx["reduction"], ctx["counters"]
    if red is None or not c.get("jobs") or not sum(red.busy_s):
        return None
    t_roof, _ = work.roofline_seconds(c["lloyd_flops"], c["lloyd_bytes"],
                                      ctx["peaks"])
    return 100.0 * t_roof / sum(red.busy_s)
