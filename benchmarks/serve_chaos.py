"""Serving chaos: multi-tenant fault injection against `repro.serve`.

The serving twin of ``benchmarks/chaos.py``.  Two phases over identical
seeded request streams:

* **clean** — multi-tenant closed-loop traffic (several healthy tenants on
  "prod", one client on "canary") with no faults: the baseline results and
  latency percentiles.
* **chaos** — the same streams while everything goes wrong at once:

  - "prod" launches fail transiently at a seeded rate (recovered on the
    ref fallback path, invisible to clients);
  - a poisoned tenant submits NaN payloads with validation off, so the
    fault fires *inside* coalesced launches and only batch bisection can
    isolate it;
  - "canary" suffers a launch outage window: its circuit breaker trips,
    fast-fails, probes half-open on the seeded backoff, and recovers when
    the outage ends;
  - a `CheckpointWatcher` on prod's checkpoint dir rides through a hung
    restore (watchdog abandons the poll) and a torn newest checkpoint
    (skipped), converging to the newest *intact* step.

Acceptance (checked before writing, exit code 1 on failure):

* every healthy-tenant request completes, bitwise-identical to the clean
  run (ids always; dists on CPU where primary and fallback share the ref
  kernel) — availability >= 99%;
* only directly-faulted requests fail, and with *typed* exceptions; zero
  hung futures (no client ever hits its assign timeout);
* the canary breaker demonstrably opened and re-closed (observed via
  `Server.health()` polling), and the server ends healthy;
* the watcher recorded the stall, skipped the torn step, and landed on the
  newest intact one;
* chaos p99 stays within 25x clean p99 (floor 250ms) for healthy tenants.

Writes BENCH_serve_chaos.json at the repo root (committed — the serving
resilience trajectory future PRs regress against).

    PYTHONPATH=src python -m benchmarks.serve_chaos [--fast] [--seed 0]
"""
from __future__ import annotations

import argparse
import os
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

K, N = 25, 20                    # paper default clustering shape
REQ_POINTS = 32                  # one request; buckets to 32/64 with linger


def _centroids(seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((K, N)).astype(
        np.float32) * 3.0


def _stream(seed: int, reqs: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((REQ_POINTS, N)).astype(np.float32)
            for _ in range(reqs)]


def _save_ckpt(directory: str, step: int, centroids: np.ndarray) -> None:
    import jax.numpy as jnp

    from repro.cluster import checkpoint
    from repro.core import bigmeans

    k, n = centroids.shape
    state = bigmeans.init_state(k, n)._replace(
        centroids=jnp.asarray(centroids), f_best=jnp.float32(1.0))
    aux = np.asarray([0, 0, 0], dtype=np.int64)
    checkpoint.save(directory, step, ((state, jnp.zeros(2, jnp.uint32)), aux))


def _config(seed: int):
    from repro.serve import ServeConfig

    return ServeConfig(
        min_bucket=32, max_batch=256, max_linger_ms=2.0, queue_depth=256,
        launch_retries=1, breaker_threshold=3, breaker_backoff_s=0.05,
        breaker_backoff_max_s=0.5, seed=seed)


class _Tenant:
    """One closed-loop client: records outcomes per request, in order."""

    def __init__(self, name: str, model_id: str, stream: list[np.ndarray],
                 *, deadline_ms: float, validate: bool = True,
                 pace_s: float = 0.0):
        self.name = name
        self.model_id = model_id
        self.stream = stream
        self.deadline_ms = deadline_ms
        self.validate = validate
        self.pace_s = pace_s
        self.results: list = []        # (ids, dists) per completed request
        self.failures: dict = {}       # exception type name -> count
        self.latencies_ms: list = []

    def run(self, srv, barrier) -> None:
        barrier.wait()
        for pts in self.stream:
            t0 = time.monotonic()
            try:
                r = srv.assign(self.model_id, pts, timeout=60.0,
                               deadline_ms=self.deadline_ms,
                               tenant=self.name, validate=self.validate)
            except Exception as exc:  # noqa: BLE001 — typed faults expected
                kind = type(exc).__name__
                self.failures[kind] = self.failures.get(kind, 0) + 1
            else:
                self.results.append((r.ids, r.dists))
                self.latencies_ms.append((time.monotonic() - t0) * 1e3)
            if self.pace_s:
                time.sleep(self.pace_s)


def _make_tenants(seed: int, *, n_healthy: int, reqs: int,
                  canary_reqs: int, poisoned: bool) -> list[_Tenant]:
    tenants = [
        _Tenant(f"tenant{i}", "prod", _stream(seed + 10 + i, reqs),
                deadline_ms=10_000.0)
        for i in range(n_healthy)
    ]
    tenants.append(_Tenant(
        "canary-client", "canary", _stream(seed + 50, canary_reqs),
        deadline_ms=2_000.0, pace_s=0.02))
    if poisoned:
        bad = _stream(seed + 99, max(reqs // 5, 4))
        for pts in bad:
            pts[1, 2] = np.nan
        tenants.append(_Tenant("poisoned", "prod", bad,
                               deadline_ms=10_000.0, validate=False,
                               pace_s=0.01))
    return tenants


def _run_clients(srv, tenants: list[_Tenant]) -> float:
    barrier = threading.Barrier(len(tenants) + 1)
    threads = [threading.Thread(target=t.run, args=(srv, barrier),
                                daemon=True) for t in tenants]
    for th in threads:
        th.start()
    barrier.wait()
    t0 = time.monotonic()
    for th in threads:
        th.join()
    return time.monotonic() - t0


def _healthy_metrics(tenants: list[_Tenant]) -> dict:
    healthy = [t for t in tenants if t.model_id == "prod"
               and t.name != "poisoned"]
    offered = sum(len(t.stream) for t in healthy)
    done = sum(len(t.results) for t in healthy)
    lats = np.asarray(sum((t.latencies_ms for t in healthy), []),
                      dtype=np.float64)
    return {
        "healthy_offered": offered,
        "healthy_completed": done,
        "availability": round(done / offered, 6) if offered else 0.0,
        "healthy_p50_ms": round(float(np.percentile(lats, 50)), 3)
        if lats.size else 0.0,
        "healthy_p99_ms": round(float(np.percentile(lats, 99)), 3)
        if lats.size else 0.0,
    }


def _wait_until(predicate, timeout_s: float) -> bool:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


def run_phase(seed: int, *, chaos: bool, n_healthy: int, reqs: int,
              canary_reqs: int, outage_after: int, outage_len: int,
              ckpt_dir: str | None) -> dict:
    from repro.engine import faults
    from repro.serve import serve

    C_prod, C_canary = _centroids(seed), _centroids(seed + 1)
    tenants = _make_tenants(seed, n_healthy=n_healthy, reqs=reqs,
                            canary_reqs=canary_reqs, poisoned=chaos)
    breaker_states: set = set()
    watcher_report: dict = {}
    row: dict = {"phase": "chaos" if chaos else "clean"}

    with serve({"prod": C_prod, "canary": C_canary}, _config(seed)) as srv:
        watcher = None
        if chaos:
            prod = srv.registry.get("prod")
            canary = srv.registry.get("canary")
            prod.launch = faults.FaultPlan(
                seed=seed, launch_transient_rate=0.08).wrap_launch(
                    prod.launch)
            canary.launch = faults.FaultPlan(
                seed=seed, launch_outage_after=outage_after,
                launch_outage_len=outage_len).wrap_launch(canary.launch)
            if ckpt_dir is not None:
                # Step 1 (same centroids: swaps stay bitwise-invisible)
                # is already on disk; the watcher picks it up and then
                # rides through a hung restore and a torn newest step.
                watcher = srv.watch("prod", ckpt_dir, poll_interval_s=0.02,
                                    poll_timeout_s=0.2)

        stop_poll = threading.Event()

        def poll_health() -> None:
            while not stop_poll.is_set():
                h = srv.health()
                breaker_states.add(h["models"]["canary"]["breaker"]["state"])
                stop_poll.wait(0.01)

        poller = threading.Thread(target=poll_health, daemon=True)
        poller.start()

        runner = threading.Thread(
            target=lambda: row.update(wall_s=round(
                _run_clients(srv, tenants), 3)), daemon=True)
        runner.start()

        if chaos and ckpt_dir is not None:
            time.sleep(0.1)                     # let traffic flow first
            with faults.hung_restore():
                _save_ckpt(ckpt_dir, 2, C_prod)  # new step, hung load
                stall_seen = _wait_until(
                    lambda: watcher.stalled_polls >= 1, 10.0)
            swap_done = _wait_until(lambda: watcher.last_step == 2, 10.0)
            _save_ckpt(ckpt_dir, 3, C_prod)
            faults.corrupt_checkpoint(ckpt_dir, step=3)   # torn write
            time.sleep(0.2)                     # a few polls on the torn dir
            watcher_report = {
                "stall_seen": stall_seen,
                "swap_done": swap_done,
                "torn_step_skipped": watcher.last_step == 2,
                **watcher.describe(),
            }

        runner.join()
        stop_poll.set()
        poller.join()

        canary_recovered = True
        if chaos:
            # The outage window is finite: keep probing until the breaker
            # closes and the canary serves again.
            def probe() -> bool:
                try:
                    srv.assign("canary", _stream(seed + 77, 1)[0],
                               timeout=10.0, tenant="probe")
                    return True
                except Exception:  # noqa: BLE001 — breaker still open
                    return False

            canary_recovered = _wait_until(probe, 20.0)

        stats_prod = srv.stats("prod")
        stats_canary = srv.stats("canary")
        health = srv.health()
        trace_kinds = sorted({e[0] for e in srv.trace})
        if watcher is not None:
            watcher.stop()

    row.update(_healthy_metrics(tenants))
    poisoned = next((t for t in tenants if t.name == "poisoned"), None)
    canary_client = next(t for t in tenants if t.model_id == "canary")
    row.update({
        "prod_launch_faults": stats_prod["n_launch_faults"],
        "prod_ref_retries": stats_prod["n_ref_retries"],
        "prod_failed": stats_prod["n_failed"],
        "canary_launch_faults": stats_canary["n_launch_faults"],
        "canary_breaker_rejected": stats_canary["n_breaker_rejected"],
        "canary_completed": len(canary_client.results),
        "canary_failures": dict(canary_client.failures),
        "canary_breaker_states_seen": sorted(breaker_states),
        "canary_recovered": canary_recovered,
        "poisoned_offered": len(poisoned.stream) if poisoned else 0,
        "poisoned_failed_typed": (poisoned.failures.get("LaunchFault", 0)
                                  if poisoned else 0),
        "poisoned_completed": len(poisoned.results) if poisoned else 0,
        "assign_timeouts": sum(
            t.failures.get("DeadlineExceeded", 0) for t in tenants
            if t.deadline_ms >= 10_000.0),
        "end_health_ok": health["ok"],
        "trace_kinds": trace_kinds,
        "worker_restarts": sum(
            m["worker_restarts"] for m in health["models"].values()),
    })
    if watcher_report:
        row["watcher"] = watcher_report
    # The per-request results ride back for the bitwise check, but stay
    # out of the serialized row.
    row["_tenants"] = tenants
    return row


def _bitwise_check(clean: dict, chaos: dict) -> dict:
    """Healthy tenants must see bitwise-identical results in both phases."""
    import jax

    exact_dists = jax.default_backend() == "cpu"
    clean_t = {t.name: t for t in clean["_tenants"]}
    mismatches = 0
    compared = 0
    for t in chaos["_tenants"]:
        if t.model_id != "prod" or t.name == "poisoned":
            continue
        ref = clean_t[t.name]
        if len(t.results) != len(ref.results):
            mismatches += abs(len(t.results) - len(ref.results))
            continue
        for (ids_a, d_a), (ids_b, d_b) in zip(ref.results, t.results):
            compared += 1
            if not np.array_equal(ids_a, ids_b):
                mismatches += 1
            elif exact_dists and not np.array_equal(d_a, d_b):
                mismatches += 1
    return {"requests_compared": compared, "mismatches": mismatches,
            "exact_dists": exact_dists}


def _acceptance(clean: dict, chaos: dict, bitwise: dict) -> dict:
    problems = []
    if chaos["availability"] < 0.99:
        problems.append(
            f"healthy availability {chaos['availability']} < 0.99")
    if bitwise["mismatches"] or bitwise["requests_compared"] == 0:
        problems.append(
            f"bitwise parity failed: {bitwise['mismatches']} mismatches "
            f"over {bitwise['requests_compared']} requests")
    if chaos["assign_timeouts"]:
        problems.append(
            f"{chaos['assign_timeouts']} hung futures (assign timeouts)")
    if chaos["poisoned_failed_typed"] + chaos["poisoned_completed"] \
            != chaos["poisoned_offered"]:
        problems.append("poisoned requests not all resolved with a typed "
                        "outcome")
    if chaos["prod_failed"] > chaos["poisoned_offered"]:
        problems.append("bisection failed more requests than were poisoned")
    if "open" not in chaos["canary_breaker_states_seen"]:
        problems.append("canary breaker never observed open via health()")
    if not chaos["canary_recovered"]:
        problems.append("canary never recovered after the outage window")
    if not chaos["end_health_ok"]:
        problems.append("server did not end healthy")
    w = chaos.get("watcher", {})
    if w and not (w["stall_seen"] and w["swap_done"]
                  and w["torn_step_skipped"] and w["alive"]):
        problems.append(f"watcher chaos ride-through failed: {w}")
    p99_bound = max(25.0 * clean["healthy_p99_ms"], 250.0)
    if chaos["healthy_p99_ms"] > p99_bound:
        problems.append(
            f"chaos p99 {chaos['healthy_p99_ms']}ms exceeds bound "
            f"{p99_bound}ms")
    summary = {
        "availability": chaos["availability"],
        "bitwise": bitwise,
        "clean_p99_ms": clean["healthy_p99_ms"],
        "chaos_p99_ms": chaos["healthy_p99_ms"],
        "p99_bound_ms": round(p99_bound, 3),
        "breaker_states_seen": chaos["canary_breaker_states_seen"],
        "watcher": {k: w[k] for k in
                    ("stall_seen", "swap_done", "torn_step_skipped",
                     "stalled_polls", "last_step")} if w else {},
        "pass": not problems,
    }
    if problems:
        summary["problems"] = problems
    return summary


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.configure()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller streams (CI smoke)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import tempfile

    from repro.evalsuite import schema as bench_schema

    n_healthy = 3 if args.fast else 6
    reqs = 40 if args.fast else 120
    canary_reqs = 40 if args.fast else 80
    outage_after, outage_len = (5, 6) if args.fast else (10, 8)

    kwargs = dict(n_healthy=n_healthy, reqs=reqs, canary_reqs=canary_reqs,
                  outage_after=outage_after, outage_len=outage_len)

    clean = run_phase(args.seed, chaos=False, ckpt_dir=None, **kwargs)
    print(f"clean : avail={clean['availability']}  "
          f"p99={clean['healthy_p99_ms']}ms  wall={clean['wall_s']}s",
          flush=True)

    with tempfile.TemporaryDirectory() as td:
        ckpt_dir = os.path.join(td, "ckpt")
        _save_ckpt(ckpt_dir, 1, _centroids(args.seed))
        chaos = run_phase(args.seed, chaos=True, ckpt_dir=ckpt_dir, **kwargs)
    print(f"chaos : avail={chaos['availability']}  "
          f"p99={chaos['healthy_p99_ms']}ms  wall={chaos['wall_s']}s  "
          f"faults={chaos['prod_launch_faults']}+"
          f"{chaos['canary_launch_faults']}  "
          f"ref_retries={chaos['prod_ref_retries']}  "
          f"breaker={chaos['canary_breaker_states_seen']}  "
          f"watcher_stalls={chaos.get('watcher', {}).get('stalled_polls')}",
          flush=True)

    bitwise = _bitwise_check(clean, chaos)
    summary = _acceptance(clean, chaos, bitwise)
    rows = []
    for row in (clean, chaos):
        row = dict(row)
        row.pop("_tenants")
        rows.append(row)

    json_path = bench_schema.write_bench(
        os.path.join(REPO, "BENCH_serve_chaos.json"),
        bench_schema.envelope(
            "serve_chaos", rows,
            shape={"k": K, "n": N, "req_points": REQ_POINTS,
                   "n_healthy_tenants": n_healthy, "reqs": reqs,
                   "seed": args.seed},
            protocol="two phases over identical seeded streams: clean "
                     "baseline, then chaos (seeded transient launch "
                     "faults recovered on the ref path, NaN-poisoned "
                     "tenant isolated by batch bisection, canary launch "
                     "outage tripping the circuit breaker, checkpoint "
                     "watcher riding a hung restore and a torn step); "
                     "healthy tenants must complete bitwise-identically "
                     "(ids always, dists on CPU) with >=99% availability "
                     "and bounded p99 degradation",
            summary=summary,
        ))
    print(f"# wrote {json_path}")
    if not summary["pass"]:
        raise SystemExit(
            "serve_chaos acceptance failed: " + "; ".join(
                summary["problems"]))


if __name__ == "__main__":
    main()
