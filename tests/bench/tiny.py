"""Tiny versions of the benchmark's cells for CPU tests."""
from bench import spec

FIT = {"m": 20_000, "s": 1_000, "rounds": 2}
SERVE = {"s": 1_000, "rate_per_s": 200}


def _shrink(c):
    c.config["dataset"]["m"] = FIT["m"]
    c.config["algorithm"]["s"] = FIT["s"]
    if c.mix["kind"] == "fit_job":
        c.mix["rounds"] = FIT["rounds"]
    else:
        c.mix["rate_per_s"] = SERVE["rate_per_s"]
    return c


def cell(name: str, root=None):
    c = spec.load_cell(name) if root is None else spec.load_cell(name, root)
    return _shrink(c)
