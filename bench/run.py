"""Run one cell of the chip benchmark once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It exits 2 without the program (``src/
repro``), and 3 when JAX finds no TPU or fewer chips than the cell asks
for; in both cases it prints no result.  Otherwise the last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, where requests failed ``failures`` by kind, and last
``checks``: each number compared with the float64 reference beside its
limit, which also end standard error.

``setup_s`` runs from process start to the opening of the measured
window: making the inputs, compiling (from the persistent cache after a
checkout's first run) and warming every shape the window uses.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time


def _process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``), else 0."""
    try:
        start_ticks = int(pathlib.Path("/proc/self/stat").read_text()
                          .rsplit(")", 1)[1].split()[19])
        uptime = float(pathlib.Path("/proc/uptime").read_text().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_START = time.monotonic() - _process_age_s()
T_MAIN = time.monotonic()

from bench import spec  # noqa: E402

TRACE_DIR = ".bench_out/trace"


class NoChip(RuntimeError):
    pass


def settle_heap() -> None:
    """Collect set-up's garbage and move what survives out of the cyclic
    collector's reach (``gc.freeze``): a full collection in the window then
    scans only the window's own objects.  Unfrozen, the 160k objects that
    JAX and set-up leave behind made each full collection a stall of
    80-110 ms (CPU probe), which set the serving tail."""
    import gc

    gc.collect()
    gc.freeze()


def device_record(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _number(x):
    """A metric value for JSON: floats unrounded; an infinite tail (the
    rank fell among failed requests) is written as null."""
    x = float(x)
    return None if math.isinf(x) or math.isnan(x) else x


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             overrides: dict | None = None, platform: str = "tpu") -> dict:
    """Set up, measure and check one run of ``cell``; returns the result."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench import peaks as peaks_lib
    from bench import trace as trace_lib

    t_jax = time.monotonic()
    devices = jax.devices()
    t_devices = time.monotonic()
    if devices[0].platform != platform or len(devices) < cell.chips:
        raise NoChip(f"JAX found {len(devices)} {devices[0].platform!r} "
                     f"device(s); the cell needs {cell.chips} {platform!r}")
    used = devices[:cell.chips]
    device = device_record(devices)
    peaks = peaks_lib.peaks(device["kind"]) if trace else None
    kind = spec.kind_module(cell).Kind(cell, seed, overrides)
    kind.setup(seconds)
    t_kind = time.monotonic()
    settle_heap()
    t_window = time.monotonic()
    setup_s = t_window - T_START
    parts = {"python_start": T_MAIN - T_START, "imports": t_jax - T_MAIN,
             "devices": t_devices - t_jax,
             **getattr(kind, "setup_parts", {}),
             "kind_setup": t_kind - t_devices, "heap": t_window - t_kind}
    print("setup_s parts: " + ", ".join(f"{k} {v:.3f}"
                                         for k, v in parts.items()),
          file=sys.stderr)
    out_dir = cell.root / TRACE_DIR / cell.name
    if trace:
        with trace_lib.capture(out_dir):
            with TraceAnnotation(trace_lib.WINDOW_SPAN):
                kind.window(seconds)
    else:
        with TraceAnnotation(trace_lib.WINDOW_SPAN):
            kind.window(seconds)
    attempted, failed = kind.attempted_failed()
    device["memory_peak_bytes"] = memory_peak(used)
    e2e = dict(kind.end_to_end(), setup_s=setup_s)
    print("window readings: "
          + ", ".join(f"{k} {v!r}" for k, v in e2e.items()), file=sys.stderr)
    counters = kind.counters()
    kind.release()
    result = {"correct": None, "attempted": attempted, "failed": failed}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        import shutil

        red = trace_lib.reduce(trace_lib.xplane_file(out_dir), cell.chips)
        shutil.rmtree(out_dir, ignore_errors=True)
        ctx = {"reduction": red, "counters": counters, "peaks": peaks,
               "chips": cell.chips}
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(cell, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = value
        device["busy_s"] = red.busy_mean_s
        device["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": [list(o) for o in red.ops],
                               "idle_gaps": [list(g) for g in red.idle_gaps]}
    else:
        metrics = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    result["metrics"] = {n: {"value": _number(v), "unit": units[n]}
                         for n, v in metrics.items()}
    result["device"] = device
    failures = getattr(kind, "failures", dict)()
    if failures:
        result["failures"] = failures
    checks = kind.check()
    result["correct"] = all(v <= limit for _, v, limit in checks)
    result["checks"] = {n: {"value": float(v), "limit": float(limit)}
                        for n, v, limit in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = spec.ROOT
    src = root / "src"
    if not (src / "repro").is_dir():
        print(f"bench.run: no program under {src}; run from the root of a "
              "checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    cell = spec.load_cell(args.workload, root)
    from repro.launch import compile_cache

    compile_cache.configure()
    import jax

    # Cache every program, however quick its compile, so that only a
    # checkout's first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"bench.run: {exc}; nothing was run", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
