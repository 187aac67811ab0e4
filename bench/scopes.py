"""Device time by program scope and idle gaps by program span.

A profile of a measured window holds what the program names itself
(``repro.spans``): the ``repro.*`` host spans of ``fit()`` and of the
serving batcher, and, in the compiled HLO of the fit program, the
``repro.fit.*`` scope of each instruction.  From a capture this module
takes, per chip used:

* :func:`scope_seconds` — the self time of every operation, by the scope of
  its instruction in the program the jobs ran (``repro.api.lower_fit``):
  ``unscoped`` where the instruction carries none, ``unmapped`` where the
  operation ran in another program or no map was given.  A chip's seconds
  sum to its busy seconds;
* :func:`program_gaps` — each idle gap of the first chip, attributed to the
  ``repro.*`` host span that overlaps it most (``no_program_span``
  otherwise), on any thread.

Profile one cell's window and print both, with the per-job device time of
each scope on the chip with the most and the serving batcher's queue wait
and launch times::

    python3 -m bench.scopes --workload hepmass.fit --seed 7 --seconds 5

``--keep DIR`` also writes the capture and the fit program's compiled HLO
text there, gzipped.  ``bench.run`` does not read any of it yet.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import json
import pathlib
import shutil
import sys

from bench import run, spec, stats
from bench import trace as trace_lib

PROGRAM_PREFIX = "repro."
NO_PROGRAM_SPAN = "no_program_span"
UNMAPPED = "unmapped"
MODULES_LINE = "XLA Modules"


def module_name(hlo_text: str) -> str:
    """``HloModule jit_batched_local, ...`` -> ``jit_batched_local``."""
    return hlo_text.split(None, 2)[1].rstrip(",")


def _read(path: pathlib.Path, chips: int):
    """(window, program host spans, per chip (op events, module events))."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    window, spans, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if e.name == trace_lib.WINDOW_SPAN:
                        window.append(span)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        spans.append(span)
        elif plane.name.startswith("/device:") and \
                plane.name.split(":")[-1].isdigit():
            lines = {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in ln.events if e.duration_ns > 0]
                     for ln in plane.lines
                     if ln.name in (trace_lib.OPS_LINE, MODULES_LINE)}
            if trace_lib.OPS_LINE in lines:
                devices.append((trace_lib._device_index(plane.name),
                                lines[trace_lib.OPS_LINE],
                                lines.get(MODULES_LINE, [])))
    if len(window) != 1:
        raise ValueError(f"expected one {trace_lib.WINDOW_SPAN} span, found "
                         f"{len(window)}")
    if len(devices) < chips:
        raise ValueError(f"trace holds {len(devices)} device op lines, "
                         f"the cell uses {chips}")
    devices.sort(key=lambda d: d[0])
    return window[0][:2], spans, [d[1:] for d in devices[:chips]]


def _clip(events, w0, w1):
    return [(n, max(a, w0), min(b, w1)) for n, a, b in events
            if b > w0 and a < w1]


def scope_seconds(path: pathlib.Path, chips: int,
                  hlo_text: str | None) -> list:
    """Per chip, ``{scope: seconds}`` of the window's operations.

    An operation takes the scope of its instruction in ``hlo_text`` (the
    compiled program's text) when it ran inside that program's module on
    the chip's module line; every other operation is :data:`UNMAPPED`."""
    from repro import spans as program_spans

    (w0, w1), _, devices = _read(path, chips)
    scopes = program_spans.op_scopes(hlo_text) if hlo_text else {}
    module = module_name(hlo_text) if hlo_text else None
    out = []
    for ops, modules in devices:
        runs = sorted((a, b) for n, a, b in modules
                      if n.split("(", 1)[0] == module)
        starts = [a for a, _ in runs]
        inside, outside = [], []
        for op in _clip(ops, w0, w1):
            j = bisect.bisect_right(starts, op[1]) - 1
            (inside if j >= 0 and op[1] < runs[j][1] else outside).append(op)
        seconds = collections.Counter()
        for name, ns in trace_lib._self_times(inside).items():
            seconds[scopes.get(name.split(" ", 1)[0], UNMAPPED)] += ns / 1e9
        seconds[UNMAPPED] += sum(trace_lib._self_times(outside).values()) / 1e9
        out.append({k: v for k, v in seconds.items() if v})
    return out


def program_gaps(path: pathlib.Path, chips: int = 1) -> dict:
    """Seconds of the first chip's idle gaps in the window, by the
    ``repro.*`` host span that overlaps each gap most."""
    (w0, w1), spans, devices = _read(path, chips)
    union = trace_lib._union([(a, b) for _, a, b in
                              _clip(devices[0][0], w0, w1)])
    gaps = trace_lib._attribute_gaps(union, w0, w1, list(spans))
    return {(NO_PROGRAM_SPAN if k == trace_lib.NO_SPAN else k): v
            for k, v in gaps.most_common()}


def per_job_ms(scopes: list, jobs: int) -> dict:
    """Milliseconds of each scope per job on the chip with the most."""
    names = {k for chip in scopes for k in chip}
    return {k: 1e3 * max(chip.get(k, 0.0) for chip in scopes) / jobs
            for k in sorted(names)}


def _fit_program_text(kind) -> str:
    """Compiled HLO text of the program a ``fit_job`` window's jobs ran."""
    from repro.api import lower_fit

    return lower_fit(kind.X, kind.cfg, method="batched",
                     key=kind._job_key(0)).compile().as_text()


def profile_cell(cell, seed: int, seconds: float, *, platform: str = "tpu",
                 keep: pathlib.Path | None = None) -> dict:
    """Set up ``cell``, capture one window, and reduce it by program scope
    and span.  The fit program is lowered and compiled after the window."""
    import jax
    from jax.profiler import TraceAnnotation

    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell.chips:
        raise run.NoChip(f"JAX found {len(devices)} {devices[0].platform!r} "
                         f"device(s); the cell needs {cell.chips} "
                         f"{platform!r}")
    kind = spec.kind_module(cell).Kind(cell, seed, None)
    kind.setup(seconds)
    run.settle_heap()
    traffic, series = cell.mix["kind"], None
    if traffic == "open_loop":
        (model,) = kind.server.models()
        series = kind.server.batcher_stats(model)
        n_req, n_launch = len(series.queue_ms), len(series.launch_ms)
    out_dir = cell.root / run.TRACE_DIR / f"{cell.name}.scopes"
    with trace_lib.capture(out_dir):
        with TraceAnnotation(trace_lib.WINDOW_SPAN):
            kind.window(seconds)
    result = {"workload": cell.name, "seed": seed,
              "end_to_end": kind.end_to_end()}
    hlo = _fit_program_text(kind) if traffic == "fit_job" else None
    if series is not None:
        if len(series.queue_ms) == series.queue_ms.maxlen:
            raise RuntimeError("the window outran the batcher's series")
        result["serve"] = _serve_summary(list(series.queue_ms)[n_req:],
                                         list(series.launch_ms)[n_launch:])
    counters = kind.counters()
    kind.release()
    path = trace_lib.xplane_file(out_dir)
    red = trace_lib.reduce(path, cell.chips)
    scopes = scope_seconds(path, cell.chips, hlo)
    result.update(
        window_s=red.window_s, busy_s=red.busy_s,
        device_scopes=scopes,
        idle_gaps_program=program_gaps(path),
        idle_gaps=dict(red.idle_gaps), device_ops=red.ops)
    if counters.get("jobs"):
        result["jobs"] = counters["jobs"]
        result["scope_ms_per_job"] = per_job_ms(scopes, counters["jobs"])
    if keep is not None:
        keep.mkdir(parents=True, exist_ok=True)
        stem = keep / cell.name.replace(".", "_")
        pathlib.Path(f"{stem}.xplane.pb.gz").write_bytes(
            gzip.compress(path.read_bytes()))
        if hlo:
            pathlib.Path(f"{stem}.hlo.txt.gz").write_bytes(
                gzip.compress(hlo.encode()))
    shutil.rmtree(out_dir, ignore_errors=True)
    return result


def _serve_summary(queue: list, launch: list) -> dict:
    return {"requests": len(queue), "launches": len(launch),
            "queue_wait_p99_ms": stats.tail(queue, 0.99) if queue else None,
            "launch_p99_ms": stats.tail(launch, 0.99) if launch else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=pathlib.Path)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.launch import compile_cache

    compile_cache.configure()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        result = profile_cell(spec.load_cell(args.workload), args.seed,
                              args.seconds, keep=args.keep)
    except run.NoChip as exc:
        print(f"bench.scopes: {exc}; nothing was run", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
