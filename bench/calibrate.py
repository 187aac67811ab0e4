"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 -m bench.calibrate --workload <cell> --seeds 1,2,... \\
        [--control precision=bf16x3:7,8,9] [--control fault=<name>:7,8,9] \\
        [--seconds 5] [--out readings.jsonl]

In one process, for each seed it sets the cell up, runs a window of
``--seconds`` at the cell's own sizes and load, and prints every number
the run compares with the float64 reference.  Each ``--control`` runs its
seeds the same way with one change: ``<key>=<value>`` on top of the
configuration's algorithm (the program's own lower-precision path), or
``fault=<name>``, a fault of ``bench.faults`` planted in the program.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

from bench import spec
from bench.run import settle_heap


def readings(cell, seed: int, seconds: float, overrides=None) -> dict:
    kind = spec.kind_module(cell).Kind(cell, seed, overrides)
    t = time.monotonic()
    kind.setup(seconds)
    settle_heap()
    kind.window(seconds)
    attempted, failed = kind.attempted_failed()
    e2e = kind.end_to_end()
    kind.release()
    checks = kind.check()
    return {"seed": seed, "attempted": attempted, "failed": failed,
            "end_to_end": e2e,
            "checks": {n: float(v) for n, v, _ in checks},
            "limits": {n: float(lim) for n, _, lim in checks},
            "wall_s": time.monotonic() - t}


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def parse_control(text: str) -> tuple:
    """``key=value:seeds`` -> (label, overrides, fault, seeds)."""
    change, seeds = text.rsplit(":", 1)
    key, value = change.split("=", 1)
    if key == "fault":
        return change, None, value, _seeds(seeds)
    return change, {key: value}, None, _seeds(seeds)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.launch import compile_cache

    compile_cache.configure()
    import jax

    from bench import faults

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("bench.calibrate: no TPU; nothing was run", file=sys.stderr)
        return 3
    cell = spec.load_cell(args.workload)
    groups = [(None, None, None, _seeds(args.seeds))] + \
        [parse_control(c) for c in args.control]
    out = pathlib.Path(args.out) if args.out else None
    if out:
        out.parent.mkdir(parents=True, exist_ok=True)
    for label, overrides, fault, seeds in groups:
        planted = faults.planted(fault) if fault else contextlib.nullcontext()
        with planted:
            for seed in seeds:
                try:
                    rec = readings(cell, seed, args.seconds, overrides)
                except Exception as exc:  # noqa: BLE001 — a control may crash
                    rec = {"seed": seed,
                           "error": f"{type(exc).__name__}: {exc}"[:2000]}
                rec.update(workload=args.workload, control=label)
                line = json.dumps(rec)
                print(line, flush=True)
                if out:
                    with out.open("a") as f:
                        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
