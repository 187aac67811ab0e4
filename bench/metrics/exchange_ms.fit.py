"""Device time of the collective operations per fit job on the chip that
spent most on them, in milliseconds (the incumbent exchange of a stream
mesh).  Nothing to read where no collective ran."""


def read(ctx):
    red, c = ctx["reduction"], ctx["counters"]
    if red is None or not c.get("jobs") or not max(red.collective_s):
        return None
    return 1e3 * max(red.collective_s) / c["jobs"]
