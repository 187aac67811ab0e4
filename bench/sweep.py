"""Sweep the offered rate of an open-loop serving cell (one process).

    python3 -m bench.sweep --workload hepmass.serve --seed 1 \\
        --rates 250,500,1000 --repeats 2 --step-seconds 10 \\
        [--trace-rate 500] [--out sweep.jsonl]

Set-up is paid once; each step runs a window of ``--step-seconds`` at one
rate (a fresh order and fresh payloads per step) and prints one JSON line:
attempted, failures by kind, p50 and p99 from the due time, the queue
depth when the window closed, how long the last request took to drain,
the generator's lateness, the server's own p99, requests per launch and
whether every answer matched the float64 reference.  ``--trace-rate``
adds one traced step at that rate with the device's idle share.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

from bench import spec, stats


def step(kind, rate: float, seconds: float, seed: int) -> dict:
    kind.mix["rate_per_s"] = rate
    kind.seed = seed
    kind.prepare(seconds, stream=seed)
    kind.window(seconds)
    e2e = kind.end_to_end()
    c = kind.counters()
    checks = kind.check()
    attempted, failed = kind.attempted_failed()
    done = kind.t_done[np.isfinite(kind.t_done)]
    last_due = kind.t0 + kind.due[-1]
    return {
        "rate": rate, "seed": seed, "attempted": attempted, "failed": failed,
        "failures": kind.failures(),
        "p50_ms": e2e["serve_p50_ms"], "p95_ms": e2e["serve_p95_ms"],
        "p99_ms": e2e["serve_p99_ms"],
        "queue_at_close": c["queue_at_close"],
        "drain_ms": float((done.max() - last_due) * 1e3) if done.size else None,
        "gen_late_p99_ms": stats.tail(c["gen_late_ms"], 0.99),
        "server_p99_ms": (stats.tail(c["server_latency_ms"], 0.99)
                          if len(c["server_latency_ms"]) else None),
        "requests_per_launch": (c["requests"] / c["launches"]
                                if c["launches"] else None),
        "correct": all(v <= lim for _, v, lim in checks),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="hepmass.serve")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--trace-rate", type=float, default=0.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(spec.ROOT / "src"))
    from repro.launch import compile_cache

    compile_cache.configure()
    import jax
    from jax.profiler import TraceAnnotation

    from bench import trace as trace_lib

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("bench.sweep: no TPU; nothing was run", file=sys.stderr)
        return 3
    cell = spec.load_cell(args.workload)
    kind = spec.kind_module(cell).Kind(cell, args.seed)
    kind.setup(args.step_seconds)
    from bench.run import settle_heap

    settle_heap()
    out = pathlib.Path(args.out) if args.out else None
    if out:
        out.parent.mkdir(parents=True, exist_ok=True)

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            with out.open("a") as f:
                f.write(line + "\n")

    n = 0
    for rate in [float(r) for r in args.rates.split(",")]:
        for _ in range(args.repeats):
            n += 1
            emit(step(kind, rate, args.step_seconds, args.seed + n))
    if args.trace_rate:
        trace_dir = spec.ROOT / ".bench_out" / "sweep_trace"
        kind.mix["rate_per_s"] = args.trace_rate
        kind.seed = args.seed + n + 1
        kind.prepare(args.step_seconds, stream=kind.seed)
        with trace_lib.capture(trace_dir):
            with TraceAnnotation(trace_lib.WINDOW_SPAN):
                kind.window(args.step_seconds)
        red = trace_lib.reduce(trace_lib.xplane_file(trace_dir), 1)
        attempted, failed = kind.attempted_failed()
        emit({"traced_rate": args.trace_rate, "attempted": attempted,
              "failed": failed, "failures": kind.failures(),
              "idle_share": red.idle_share, "window_s": red.window_s,
              "ops": red.ops, "idle_gaps": red.idle_gaps,
              **kind.end_to_end()})
    kind.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
