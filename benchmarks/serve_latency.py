"""Serving latency/throughput: coalesced batching vs request-at-a-time.

Measures the `repro.serve` subsystem end to end with closed-loop client
threads (each submits its next request as soon as the previous response
lands, so the offered load is exactly ``clients`` concurrent requests):

* **per_request** — the baseline the batcher replaces: ``max_batch`` is
  one request's bucket, so every launch carries exactly one request.
* **batched** — the coalescing frontend at several ``max_linger_ms``
  settings (0 = launch as soon as the worker is free, >0 = hold the first
  request briefly to pack concurrent clients into one launch).

Every cell records submit-to-completion latency percentiles (queueing and
linger included), request/point throughput, the realized
requests-per-launch, and the jit recompile counter delta after bucket
warmup — which must be **zero**: the power-of-two shape buckets are the
whole point.  A final cell re-runs the batched config while a background
thread hot-swaps the serving centroids mid-traffic and checks that every
offered request completes (no drops) across multiple centroid versions.

Writes BENCH_serve.json at the repo root (committed — the serving perf
trajectory future PRs regress against) and results/serve_latency.csv.

    PYTHONPATH=src python -m benchmarks.serve_latency [--fast]
"""
from __future__ import annotations

import argparse
import csv
import os
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

K, N = 25, 20                    # paper default clustering shape
REQ_POINTS = 48                  # one client request; buckets to 64


def _centroids(seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((K, N)).astype(
        np.float32) * 3.0


def _client_requests(clients: int, reqs: int) -> list[list[np.ndarray]]:
    rng = np.random.default_rng(1)
    return [[rng.standard_normal((REQ_POINTS, N)).astype(np.float32)
             for _ in range(reqs)] for _ in range(clients)]


def _cell_config(mode: str, linger_ms: float):
    from repro.serve import ServeConfig
    from repro.serve.config import _next_pow2

    bucket = _next_pow2(REQ_POINTS)
    if mode == "per_request":
        # one request per launch, by construction: a second request of
        # REQ_POINTS rows can never fit under max_batch.
        return ServeConfig(min_bucket=bucket, max_batch=bucket,
                           max_linger_ms=0.0, queue_depth=1024)
    return ServeConfig(min_bucket=bucket, max_batch=4096,
                       max_linger_ms=linger_ms, queue_depth=1024)


def _run_cell(mode: str, linger_ms: float, clients: int, reqs: int,
              C: np.ndarray, *, swapper: bool = False) -> dict:
    """One (mode, linger, offered-load) cell of the sweep."""
    from repro.serve import serve

    requests = _client_requests(clients, reqs)
    versions: list[set] = [set() for _ in range(clients)]
    completed = [0] * clients
    errors: list[str] = []

    with serve({"m": C}, _cell_config(mode, linger_ms)) as srv:
        warm = srv.recompiles("m")
        barrier = threading.Barrier(clients + 1)

        def client(cid: int) -> None:
            barrier.wait()
            for pts in requests[cid]:
                try:
                    r = srv.assign("m", pts, timeout=300)
                except Exception as exc:
                    errors.append(f"{type(exc).__name__}: {exc}")
                    return
                versions[cid].add(r.version)
                completed[cid] += 1

        threads = [threading.Thread(target=client, args=(cid,), daemon=True)
                   for cid in range(clients)]
        for t in threads:
            t.start()

        stop_swap = threading.Event()
        n_swaps = 0

        def swap_loop() -> None:
            nonlocal n_swaps
            seed = 100
            while not stop_swap.is_set():
                srv.swap("m", _centroids(seed))
                n_swaps += 1
                seed += 1
                stop_swap.wait(0.02)

        swap_thread = None
        if swapper:
            swap_thread = threading.Thread(target=swap_loop, daemon=True)

        barrier.wait()
        t0 = time.monotonic()
        if swap_thread is not None:
            swap_thread.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        if swap_thread is not None:
            stop_swap.set()
            swap_thread.join()

        stats = srv.stats("m")
        recompiles_post = srv.recompiles("m") - warm

    offered = clients * reqs
    done = sum(completed)
    seen = set().union(*versions) if versions else set()
    row = {
        "mode": mode,
        "linger_ms": linger_ms,
        "clients": clients,
        "reqs_per_client": reqs,
        "req_points": REQ_POINTS,
        "offered": offered,
        "completed": done,
        "dropped": offered - done,
        "errors": len(errors),
        "wall_s": round(wall, 3),
        "requests_per_s": round(done / wall, 1),
        "points_per_s": round(done * REQ_POINTS / wall, 1),
        "p50_ms": round(stats.get("p50_ms", 0.0), 3),
        "p99_ms": round(stats.get("p99_ms", 0.0), 3),
        "requests_per_batch": round(stats["requests_per_batch"], 2),
        "n_batches": stats["n_batches"],
        "n_rejected": stats["n_rejected"],
        "recompiles_post_warmup": recompiles_post,
        "n_swaps": n_swaps,
        "versions_observed": len(seen),
    }
    if errors:
        row["first_error"] = errors[0]
    return row


def bench(clients_sweep: tuple, reqs: int, lingers: tuple) -> list[dict]:
    C = _centroids()
    rows = []
    for clients in clients_sweep:
        cells = [("per_request", 0.0)] + [("batched", lg) for lg in lingers]
        for mode, linger in cells:
            row = _run_cell(mode, linger, clients, reqs, C)
            rows.append(row)
            print(f"{mode:12s} linger={linger:4.1f}ms clients={clients:<3d} "
                  f"req/s={row['requests_per_s']:8.1f}  "
                  f"p50={row['p50_ms']:7.2f}ms  p99={row['p99_ms']:7.2f}ms  "
                  f"req/batch={row['requests_per_batch']:5.2f}  "
                  f"recompiles={row['recompiles_post_warmup']}", flush=True)
    # hot-swap under the heaviest batched load
    row = _run_cell("batched_swap", lingers[-1], max(clients_sweep), reqs, C,
                    swapper=True)
    rows.append(row)
    print(f"{'batched_swap':12s} swaps={row['n_swaps']:<4d} "
          f"versions={row['versions_observed']:<3d} "
          f"dropped={row['dropped']}  req/s={row['requests_per_s']:8.1f}",
          flush=True)
    return rows


def _acceptance(rows: list[dict]) -> dict:
    """The claims this artifact commits to (checked before writing)."""
    by_clients: dict[int, dict[str, float]] = {}
    for r in rows:
        if r["mode"] in ("per_request", "batched"):
            cell = by_clients.setdefault(r["clients"], {})
            key = r["mode"]
            cell[key] = max(cell.get(key, 0.0), r["requests_per_s"])
    heavy = max(by_clients)
    speedup = by_clients[heavy]["batched"] / by_clients[heavy]["per_request"]
    swap_row = next(r for r in rows if r["mode"] == "batched_swap")
    summary = {
        "heaviest_load_clients": heavy,
        "batched_vs_per_request_speedup": round(speedup, 2),
        "recompiles_post_warmup_total": sum(
            r["recompiles_post_warmup"] for r in rows),
        "swap_under_load": {
            "n_swaps": swap_row["n_swaps"],
            "versions_observed": swap_row["versions_observed"],
            "offered": swap_row["offered"],
            "dropped": swap_row["dropped"],
        },
    }
    problems = []
    if speedup <= 1.0:
        problems.append(
            f"batched ({by_clients[heavy]['batched']} req/s) did not beat "
            f"per-request ({by_clients[heavy]['per_request']} req/s)")
    if summary["recompiles_post_warmup_total"] != 0:
        problems.append("serving recompiled after bucket warmup")
    if swap_row["dropped"] != 0 or swap_row["errors"] != 0:
        problems.append("hot-swap under load dropped requests")
    if swap_row["versions_observed"] < 2:
        problems.append("hot-swap cell never observed a second version")
    summary["pass"] = not problems
    if problems:
        summary["problems"] = problems
    return summary


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.configure()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer clients/requests (CI smoke)")
    args = ap.parse_args()

    from repro.evalsuite import schema as bench_schema

    clients_sweep = (2, 8) if args.fast else (2, 8, 32)
    reqs = 40 if args.fast else 150
    lingers = (1.0,) if args.fast else (1.0, 5.0)

    rows = bench(clients_sweep, reqs, lingers)
    summary = _acceptance(rows)

    os.makedirs(os.path.join(REPO, "results"), exist_ok=True)
    csv_path = os.path.join(REPO, "results", "serve_latency.csv")
    fields = sorted({f for r in rows for f in r})
    with open(csv_path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=fields)
        w.writeheader()
        w.writerows(rows)

    json_path = bench_schema.write_bench(
        os.path.join(REPO, "BENCH_serve.json"),
        bench_schema.envelope(
            "serve_latency", rows,
            shape={"k": K, "n": N, "req_points": REQ_POINTS},
            protocol="closed-loop clients (offered load = clients); "
                     "latency = submit-to-completion incl. queueing/linger; "
                     "per_request mode caps max_batch at one request's "
                     "bucket so every launch carries exactly one request",
            summary=summary,
        ))
    print(f"# wrote {json_path} and {csv_path}")
    if not summary["pass"]:
        raise SystemExit(
            "serve_latency acceptance failed: " + "; ".join(summary["problems"]))


if __name__ == "__main__":
    main()
