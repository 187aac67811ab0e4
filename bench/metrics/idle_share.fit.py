"""Share of the traced window in which no operation ran on the chips (mean
over the chips used), in percent."""


def read(ctx):
    red = ctx["reduction"]
    return None if red is None else 100.0 * red.idle_share
