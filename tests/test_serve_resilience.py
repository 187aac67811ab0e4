"""repro.serve resilience: typed failure, isolation, supervision.

The contracts under test (this PR's acceptance criteria):

* no request future is ever stranded — a crashed worker fails its pending
  futures with `WorkerCrashed` and restarts (the regression for the
  exception-escaping-`_take_batch` bug that used to kill the worker
  silently while `submit` kept accepting);
* `assign(timeout=)` *cancels* its queued request on timeout — no launch
  slot is burned for a client that gave up, and its latency never enters
  the percentiles;
* a non-finite payload is a typed *client* error at submit time; with
  validation off, bisection isolates the poisoned request at launch time
  and its coalesced neighbors are served bitwise-identically to a
  fault-free run;
* deadlines shed expired requests from a saturated queue before they can
  waste a launch slot, in queue order, with trace events;
* the per-model circuit breaker trips on consecutive launch failures,
  fast-fails while open, probes half-open on a seeded backoff, and closes
  on recovery — observable end-to-end through `Server.health()`;
* per-tenant quotas bound one noisy tenant without starving others;
* transient launch faults recover on the ref fallback path with bitwise
  parity; repeated primary failures demote the bucket;
* a hung checkpoint load stalls one watcher poll (counted, abandoned),
  never the watcher thread.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import checkpoint
from repro.core import bigmeans
from repro.engine import faults
from repro.kernels import ops
from repro.serve import (
    CheckpointWatcher,
    CircuitBreaker,
    DeadlineExceeded,
    InvalidRequest,
    LaunchFault,
    ModelRegistry,
    ModelUnhealthy,
    QuotaExceeded,
    ServeConfig,
    WorkerCrashed,
    serve,
)
from repro.serve.resilience import CLOSED, HALF_OPEN, OPEN


def _centroids(k: int, n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((k, n)).astype(
        np.float32) * 3.0


def _points(m: int, n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((m, n)).astype(
        np.float32)


_jit_ref = jax.jit(lambda q, c: ops.assign(q, c, impl="ref"))


def _oracle(points: np.ndarray, centroids: np.ndarray,
            rows: int | None = None):
    """Jitted ref assignment of ``points``, zero-padded to ``rows`` — the
    launch's bucket (``AssignResponse.batch_rows``) when comparing bitwise.

    XLA:CPU's distance program depends on the row count: with jax 0.9.0 a
    3-, 5- or 9-row call rounds some distances 1 ULP differently from the
    same rows inside a 64-row call, while a row's offset within one shape
    never matters.  So the oracle runs at the shape serving launched.
    """
    m = points.shape[0]
    if rows is not None:
        points = np.pad(points, ((0, rows - m), (0, 0)))
    ids, d = _jit_ref(jnp.asarray(points), jnp.asarray(centroids))
    return np.asarray(ids)[:m], np.asarray(d)[:m]


def _quick_cfg(**overrides) -> ServeConfig:
    base = dict(min_bucket=8, max_batch=64, max_linger_ms=2.0,
                queue_depth=64)
    base.update(overrides)
    return ServeConfig(**base)


def _gate_launch(entry):
    """Block the worker's launches on an Event (release with .set())."""
    gate = threading.Event()
    original = entry.launch

    def gated(q, snap):
        gate.wait(10.0)
        return original(q, snap)

    entry.launch = gated
    return gate


def _drain(batcher, timeout=5.0):
    t0 = time.monotonic()
    while batcher.queue_depth() and time.monotonic() - t0 < timeout:
        time.sleep(0.005)


# ---------------------------------------------------------------------------
# supervision: no stranded futures, ever


def test_worker_crash_fails_pending_futures_and_restarts():
    # The regression this PR exists for: before supervision, an exception
    # escaping the take/launch loop killed the worker thread silently —
    # every pending future hung forever while submit() kept accepting.
    C = _centroids(6, 4)
    with serve({"m": C}, _quick_cfg()) as srv:
        batcher = srv._batchers["m"]
        original = batcher._launch_batch

        def boom(batch):
            batcher._launch_batch = original       # crash exactly once
            raise RuntimeError("injected worker crash")

        batcher._launch_batch = boom
        fut = srv.submit("m", _points(3, 4, seed=1))
        with pytest.raises(WorkerCrashed):
            fut.result(timeout=5.0)
        # The supervisor restarted the loop: same worker thread, serving.
        resp = srv.assign("m", _points(5, 4, seed=2), timeout=5.0)
        ids, _ = _oracle(_points(5, 4, seed=2), C)
        assert np.array_equal(resp.ids, ids)
        assert batcher.worker_alive()
        assert batcher.stats.worker_restarts == 1
        assert any(e[0] == "worker_restart" and e[1] == "m"
                   for e in srv.trace)
        health = srv.health()
        assert health["models"]["m"]["worker_restarts"] == 1


def test_close_after_crash_still_clean():
    C = _centroids(4, 3)
    srv = serve({"m": C}, _quick_cfg())
    batcher = srv._batchers["m"]
    batcher._launch_batch = lambda batch: (_ for _ in ()).throw(
        RuntimeError("always crash"))
    with pytest.raises(WorkerCrashed):
        srv.submit("m", _points(2, 3, seed=0)).result(timeout=5.0)
    srv.close()
    assert not batcher.worker_alive()


# ---------------------------------------------------------------------------
# assign(timeout=): cancel, don't strand


def test_assign_timeout_cancels_queued_request():
    C = _centroids(5, 4)
    with serve({"m": C}, _quick_cfg()) as srv:
        entry = srv.registry.get("m")
        batcher = srv._batchers["m"]
        gate = _gate_launch(entry)
        blocker = srv.submit("m", _points(2, 4, seed=0))
        time.sleep(0.05)                          # worker now inside launch
        with pytest.raises(DeadlineExceeded):
            srv.assign("m", _points(2, 4, seed=1), timeout=0.05)
        # The timed-out request was withdrawn from the queue: nothing
        # pending but the blocker, and the cancellation was counted.
        assert batcher.queue_depth() == 0
        assert batcher.stats.n_cancelled == 1
        gate.set()
        blocker.result(timeout=5.0)
        _drain(batcher)
        # Cancelled requests never enter the latency percentiles.
        assert len(batcher.stats.latencies_ms) == 1


def test_cancelled_request_burns_no_launch(monkeypatch):
    C = _centroids(5, 4)
    with serve({"m": C}, _quick_cfg()) as srv:
        entry = srv.registry.get("m")
        gate = _gate_launch(entry)
        blocker = srv.submit("m", _points(2, 4, seed=0))
        time.sleep(0.05)
        fut = srv.submit("m", _points(2, 4, seed=1))
        assert srv._batchers["m"].cancel(fut)
        launches = []
        original = entry.launch

        def counting(q, snap):
            launches.append(int(q.shape[0]))
            return original(q, snap)

        entry.launch = counting
        gate.set()
        blocker.result(timeout=5.0)
        assert fut.cancelled()
        # Only the blocker launched; the cancelled request never did.
        assert len(launches) <= 1


# ---------------------------------------------------------------------------
# admission validation


def test_non_finite_request_rejected_at_submit():
    C = _centroids(4, 3)
    with serve({"m": C}, _quick_cfg()) as srv:
        bad = _points(4, 3, seed=0)
        bad[2, 1] = np.nan
        with pytest.raises(InvalidRequest):
            srv.submit("m", bad)
        inf = _points(4, 3, seed=1)
        inf[0, 0] = np.inf
        with pytest.raises(InvalidRequest):
            srv.assign("m", inf)
        assert srv.stats("m")["n_invalid"] == 2
        # Trusted-client override: admitted (the ref path tolerates NaN).
        resp = srv.assign("m", bad, validate=False, timeout=5.0)
        assert resp.ids.shape == (4,)


def test_deadline_must_be_positive():
    C = _centroids(4, 3)
    with serve({"m": C}, _quick_cfg()) as srv:
        with pytest.raises(ValueError):
            srv.submit("m", _points(2, 3, seed=0), deadline_ms=0)


# ---------------------------------------------------------------------------
# deadline shedding


def test_deadlines_shed_expired_requests_under_saturation():
    C = _centroids(5, 4)
    with serve({"m": C}, _quick_cfg()) as srv:
        entry = srv.registry.get("m")
        batcher = srv._batchers["m"]
        gate = _gate_launch(entry)
        blocker = srv.submit("m", _points(2, 4, seed=0))
        time.sleep(0.05)
        # Saturated queue: one request with a deadline that will expire
        # while blocked, one without any deadline.
        doomed = srv.submit("m", _points(2, 4, seed=1), deadline_ms=40.0)
        healthy = srv.submit("m", _points(2, 4, seed=2))
        time.sleep(0.12)                          # doomed is now expired
        gate.set()
        blocker.result(timeout=5.0)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=5.0)
        resp = healthy.result(timeout=5.0)
        ids, _ = _oracle(_points(2, 4, seed=2), C)
        assert np.array_equal(resp.ids, ids)
        assert batcher.stats.n_deadline_shed == 1
        shed = [e for e in srv.trace if e[0] == "deadline_shed"]
        assert len(shed) == 1 and shed[0][1] == "m" and shed[0][2] > 0


def test_default_deadline_from_config():
    C = _centroids(5, 4)
    with serve({"m": C}, _quick_cfg(default_deadline_ms=40.0)) as srv:
        entry = srv.registry.get("m")
        gate = _gate_launch(entry)
        blocker = srv.submit("m", _points(2, 4, seed=0))
        time.sleep(0.05)
        doomed = srv.submit("m", _points(2, 4, seed=1))  # inherits 40ms
        time.sleep(0.12)
        gate.set()
        blocker.result(timeout=5.0)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=5.0)


# ---------------------------------------------------------------------------
# per-tenant quotas


def test_tenant_quota_bounds_one_tenant_not_others():
    C = _centroids(4, 3)
    with serve({"m": C}, _quick_cfg(tenant_quota=2)) as srv:
        entry = srv.registry.get("m")
        gate = _gate_launch(entry)
        blocker = srv.submit("m", _points(2, 3, seed=0), tenant="noisy")
        time.sleep(0.05)
        # The blocker is in flight (not queued): tenant "noisy" may queue
        # two more, then hits its quota while "quiet" still admits.
        futs = [srv.submit("m", _points(2, 3, seed=i), tenant="noisy")
                for i in (1, 2)]
        with pytest.raises(QuotaExceeded):
            srv.submit("m", _points(2, 3, seed=3), tenant="noisy")
        quiet = srv.submit("m", _points(2, 3, seed=4), tenant="quiet")
        gate.set()
        for f in [blocker, quiet] + futs:
            f.result(timeout=5.0)
        assert srv.stats("m")["n_quota_rejected"] == 1
        # Quota freed after the queue drained: the tenant admits again.
        srv.assign("m", _points(2, 3, seed=5), tenant="noisy", timeout=5.0)


# ---------------------------------------------------------------------------
# circuit breaker


def test_breaker_state_machine_with_fake_clock():
    t = [0.0]
    events = []
    br = CircuitBreaker("m", threshold=3, backoff_s=1.0, backoff_max_s=8.0,
                        seed=7, clock=lambda: t[0], on_event=events.append)
    assert br.allow() and br.state == CLOSED
    br.record_failure("f1")
    br.record_failure("f2")
    assert br.allow()                             # still under threshold
    br.record_failure("f3")
    assert br.state == OPEN and not br.allow()
    assert 0.0 < br.retry_in_s() <= 1.0           # jittered in (0.5, 1.0]
    # Backoff expires: exactly one caller becomes the half-open probe.
    t[0] = 1.0
    assert br.allow() and br.state == HALF_OPEN
    assert not br.allow()                         # probe already in flight
    # Probe fails: re-open with doubled backoff.
    br.record_failure("probe failed")
    assert br.state == OPEN and br.trips == 2
    assert br.retry_in_s() <= 2.0
    t[0] = 3.0
    assert br.allow()
    br.record_success()
    assert br.state == CLOSED and br.failures == 0
    kinds = [e[0] for e in events]
    assert kinds == ["breaker_open", "breaker_probe", "breaker_open",
                     "breaker_probe", "breaker_close"]
    # Determinism: two identically seeded breakers probe at the same offsets.
    def trip_once(seed):
        b = CircuitBreaker("m", threshold=3, backoff_s=1.0,
                           backoff_max_s=8.0, seed=seed, clock=lambda: 0.0)
        for _ in range(3):
            b.record_failure()
        return b.retry_in_s()

    assert trip_once(7) == trip_once(7)
    assert trip_once(7) != trip_once(8)


def test_breaker_trips_fast_fails_and_recovers_end_to_end():
    C = _centroids(5, 4)
    cfg = _quick_cfg(breaker_threshold=3, breaker_backoff_s=0.05,
                     breaker_backoff_max_s=0.05, launch_retries=0)
    with serve({"m": C}, cfg) as srv:
        entry = srv.registry.get("m")
        original = entry.launch

        def dead(q, snap):
            raise faults.PermanentFault("injected model outage")

        entry.launch = dead
        entry.launch_fallback = dead
        for i in range(3):
            with pytest.raises(LaunchFault):
                srv.assign("m", _points(2, 4, seed=i), timeout=5.0)
        # Breaker is open: requests fast-fail without touching the queue.
        with pytest.raises(ModelUnhealthy) as exc_info:
            srv.submit("m", _points(2, 4, seed=9))
        assert exc_info.value.retry_in_s > 0
        health = srv.health()
        assert health["models"]["m"]["breaker"]["state"] == OPEN
        assert not health["ok"]
        # Model heals; the half-open probe succeeds and closes the breaker.
        entry.launch = original
        del entry.launch_fallback                 # restore class method
        time.sleep(0.08)
        resp = srv.assign("m", _points(3, 4, seed=10), timeout=5.0)
        ids, _ = _oracle(_points(3, 4, seed=10), C)
        assert np.array_equal(resp.ids, ids)
        health = srv.health()
        assert health["models"]["m"]["breaker"]["state"] == CLOSED
        assert health["ok"]
        kinds = [e[0] for e in srv.trace]
        assert "breaker_open" in kinds and "breaker_probe" in kinds \
            and "breaker_close" in kinds
        assert srv.stats("m")["n_breaker_rejected"] == 1


# ---------------------------------------------------------------------------
# fault-isolated launches


def test_bisection_isolates_poisoned_request_bitwise():
    C = _centroids(6, 4)
    # Generous linger so all requests coalesce into one launch behind the
    # blocked worker; the injected launch wrapper fails any payload that
    # carries non-finite values (a kernel choking on a poisoned request).
    cfg = _quick_cfg(max_linger_ms=100.0, launch_retries=0)
    with serve({"m": C}, cfg) as srv:
        entry = srv.registry.get("m")
        plan = faults.FaultPlan(seed=3)
        entry.launch = plan.wrap_launch(entry.launch)
        gate = _gate_launch(entry)                # gates the wrapped launch
        blocker = srv.submit("m", _points(2, 4, seed=0))
        time.sleep(0.05)
        healthy_pts = [_points(3, 4, seed=10 + i) for i in range(4)]
        poison = _points(3, 4, seed=99)
        poison[1, 2] = np.nan
        futs = [srv.submit("m", p) for p in healthy_pts[:2]]
        poisoned = srv.submit("m", poison, validate=False)
        futs += [srv.submit("m", p) for p in healthy_pts[2:]]
        gate.set()
        blocker.result(timeout=5.0)
        # Only the poisoned request fails, and with the typed exception.
        with pytest.raises(LaunchFault):
            poisoned.result(timeout=10.0)
        for pts, fut in zip(healthy_pts, futs):
            resp = fut.result(timeout=10.0)
            ids, dists = _oracle(pts, C, resp.batch_rows)
            assert np.array_equal(resp.ids, ids)
            assert np.array_equal(resp.dists, dists)
        assert srv.stats("m")["n_failed"] == 1
        assert any(e[0] == "launch_fault" for e in srv.trace)
        # One poisoned request among healthy traffic must not trip the
        # breaker: healthy sub-launches reset the consecutive count.
        assert srv.health()["models"]["m"]["breaker"]["state"] == CLOSED


def test_transient_launch_faults_recover_on_ref_path_bitwise():
    C = _centroids(5, 4)
    with serve({"m": C}, _quick_cfg(launch_retries=1, demote_after=0)) as srv:
        entry = srv.registry.get("m")
        # Every primary launch fails transiently; the ref fallback serves.
        plan = faults.FaultPlan(seed=0, launch_transient_rate=1.0)
        entry.launch = plan.wrap_launch(entry.launch)
        for i in range(4):
            pts = _points(6, 4, seed=i)
            resp = srv.assign("m", pts, timeout=5.0)
            ids, dists = _oracle(pts, C, resp.batch_rows)
            assert np.array_equal(resp.ids, ids)
            assert np.array_equal(resp.dists, dists)
        stats = srv.stats("m")
        assert stats["n_ref_retries"] == 4
        assert stats["n_failed"] == 0
        assert srv.health()["models"]["m"]["breaker"]["state"] == CLOSED


def test_repeated_primary_failures_demote_bucket():
    C = _centroids(5, 4)
    cfg = _quick_cfg(launch_retries=1, demote_after=2)
    with serve({"m": C}, cfg) as srv:
        entry = srv.registry.get("m")
        plan = faults.FaultPlan(seed=0, launch_transient_rate=1.0)
        entry.launch = plan.wrap_launch(entry.launch)
        for i in range(3):
            srv.assign("m", _points(6, 4, seed=i), timeout=5.0)
        # After demote_after consecutive primary failures at the 8-bucket,
        # the batcher pinned it to the ref path...
        assert entry.demoted_buckets == (8,)
        assert srv.health()["models"]["m"]["demoted_buckets"] == [8]
        # ...so later launches at that bucket bypass the failing primary
        # entirely: the wrapped launch is not called again.
        calls_before = entry.launch.calls["n"]
        resp = srv.assign("m", _points(6, 4, seed=9), timeout=5.0)
        ids, _ = _oracle(_points(6, 4, seed=9), C)
        assert np.array_equal(resp.ids, ids)
        assert entry.launch.calls["n"] == calls_before


# ---------------------------------------------------------------------------
# watcher supervision


def _save_engine_ckpt(directory: str, step: int, centroids: np.ndarray):
    k, n = centroids.shape
    state = bigmeans.init_state(k, n)._replace(
        centroids=jnp.asarray(centroids),
        f_best=jnp.float32(1.0))
    aux = np.asarray([0, 0, 0], dtype=np.int64)
    checkpoint.save(directory, step, ((state, jnp.zeros(2, jnp.uint32)), aux))


def test_watcher_survives_poll_exceptions(monkeypatch):
    registry = ModelRegistry()
    C = _centroids(4, 3)
    registry.register("m", C)
    w = CheckpointWatcher(registry, "m", "/nonexistent",
                          poll_interval_s=0.01, poll_timeout_s=None)

    def explode(_):
        raise OSError("injected scan failure")

    monkeypatch.setattr(checkpoint, "latest_intact_step", explode)
    w.start()
    time.sleep(0.1)
    assert w.alive()                              # the scan error didn't
    assert w.n_errors > 0                         # kill the thread
    assert "injected scan failure" in w.last_error
    w.stop()
    d = w.describe()
    assert d["n_errors"] == w.n_errors and d["model_id"] == "m"


def test_watcher_watchdog_abandons_hung_poll(tmp_path):
    d = str(tmp_path / "ckpt")
    C = _centroids(4, 3)
    C2 = _centroids(4, 3, seed=1)
    _save_engine_ckpt(d, 1, C)
    registry = ModelRegistry()
    registry.register("m", C)
    w = CheckpointWatcher(registry, "m", d, poll_interval_s=0.02,
                          poll_timeout_s=0.1)
    with faults.hung_restore():                   # loads hang until exit
        w.start()
        _save_engine_ckpt(d, 2, C2)               # a new step appears...
        t0 = time.monotonic()
        while w.stalled_polls == 0 and time.monotonic() - t0 < 5.0:
            time.sleep(0.01)
        # ...but its load hangs: the watchdog abandoned the poll instead
        # of freezing the watcher thread, and no swap happened.
        assert w.stalled_polls >= 1
        assert w.alive()
        assert w.n_swaps == 0
        assert "stalled" in w.last_error
        assert any(e[0] == "watcher_stall" for e in registry.trace)
    # Filesystem recovers: the abandoned poll completes (possibly at the
    # older step it had already chosen) and a fresh poll converges the
    # watcher forward to the newest intact step.
    t0 = time.monotonic()
    while w.last_step != 2 and time.monotonic() - t0 < 5.0:
        time.sleep(0.02)
    assert w.n_swaps >= 1 and w.last_step == 2
    assert np.array_equal(
        np.asarray(registry.get("m").snapshot().centroids), C2)
    w.stop()


# ---------------------------------------------------------------------------
# health aggregation


def test_health_shape_and_ok():
    C = _centroids(4, 3)
    with serve({"a": C, "b": _centroids(5, 3, seed=2)},
               _quick_cfg()) as srv:
        srv.assign("a", _points(3, 3, seed=0), timeout=5.0)
        health = srv.health()
        assert health["ok"] is True
        assert set(health["models"]) == {"a", "b"}
        m = health["models"]["a"]
        assert m["queue_depth"] == 0
        assert m["worker_alive"] is True
        assert m["worker_restarts"] == 0
        assert m["breaker"]["state"] == CLOSED
        assert m["demoted_buckets"] == []
        assert m["last_swap_age_s"] >= 0
        assert health["watchers"] == []
        # health() is JSON-serializable (the ops endpoint contract).
        import json

        json.dumps(health)
