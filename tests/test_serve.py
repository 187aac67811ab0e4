"""repro.serve: batching frontend, multi-model tenancy, hot-swap.

The contracts under test (the serving subsystem's acceptance criteria):

* coalescing is invisible — a request's results are bitwise-identical
  whether it rode a coalesced launch or its own, across bucket boundaries;
* after bucket warmup the jitted serving call never recompiles, whatever
  request sizes traffic throws at it (exact trace counter);
* hot-swap under concurrent traffic loses no request and never mixes old
  and new centroids within one response;
* tenants are isolated: two resident models serve concurrently, each
  bitwise-correct against its own centroids, with separate accounting;
* a full queue rejects loudly and immediately (never a hang), and the
  rejected client can retry once the queue drains;
* serving-shaped Pallas failures demote per-shape at warmup through
  `ops.warm_assign` — the request path then runs the ref fallback.
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
    ModelRegistry,
    QueueFull,
    ServeConfig,
    Server,
    ServerClosed,
    load_centroids,
    serve,
    swap_from_checkpoint,
)

RNG = np.random.default_rng(7)


def _centroids(k: int, n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((k, n)).astype(
        np.float32) * 3.0


def _points(m: int, n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((m, n)).astype(
        np.float32)


# The serving path always runs under jit; XLA fuses the distance expression
# differently eager vs jitted (1-ULP dist differences), so the bitwise
# oracle must be jitted too.  Padding/bucket row-independence is what the
# tests then actually measure: the oracle runs at the request's own shape,
# serving runs at the padded bucket shape.
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


# ---------------------------------------------------------------------------
# config contract


def test_config_validation():
    assert ServeConfig().buckets()[-1] == 4096
    assert ServeConfig(min_bucket=8, max_batch=64).buckets() == (8, 16, 32, 64)
    # non-power-of-two knobs round up, bucket chain stays power-of-two
    assert ServeConfig(min_bucket=6, max_batch=48).buckets() == (8, 16, 32, 64)
    with pytest.raises(ValueError):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError):
        ServeConfig(min_bucket=128, max_batch=64)
    with pytest.raises(ValueError):
        ServeConfig(max_linger_ms=-1.0)
    with pytest.raises(ValueError):
        ServeConfig(impl="nope")
    with pytest.raises(ValueError):
        ServeConfig(precision="f64")
    with pytest.raises(ValueError):
        ServeConfig(donate="maybe")


def test_submit_validation():
    C = _centroids(5, 4)
    with serve({"m": C}, _quick_cfg()) as srv:
        with pytest.raises(ValueError):          # wrong feature count
            srv.assign("m", _points(3, 7, 0))
        with pytest.raises(ValueError):          # oversized request
            srv.assign("m", _points(65, 4, 0))
        with pytest.raises(ValueError):          # empty request
            srv.assign("m", np.zeros((0, 4), np.float32))
        with pytest.raises(KeyError):
            srv.assign("ghost", _points(3, 4, 0))
        # a 1-D query is promoted to one row
        resp = srv.assign("m", _points(1, 4, 0)[0])
        assert resp.ids.shape == (1,)


# ---------------------------------------------------------------------------
# coalescing correctness


def test_coalesced_bitwise_equal_per_request_across_buckets():
    """Concurrent (coalesced) and serial (one-per-launch) serving return
    bitwise-identical ids AND distances, for request sizes straddling
    every bucket boundary."""
    C = _centroids(10, 12)
    sizes = [3, 8, 9, 16, 5, 1, 31, 64]          # crosses 8/16/32/64
    reqs = [_points(m, 12, seed=100 + i) for i, m in enumerate(sizes)]

    # serial: linger 0 and one request in flight at a time
    with serve({"m": C}, _quick_cfg(max_linger_ms=0.0)) as srv:
        serial = [srv.assign("m", p) for p in reqs]
    assert all(r.n_coalesced == 1 for r in serial)

    # concurrent: long linger, submit everything before reading results
    with serve({"m": C}, _quick_cfg(max_linger_ms=100.0)) as srv:
        futures = [srv.submit("m", p) for p in reqs]
        coalesced = [f.result(timeout=30) for f in futures]
    assert any(r.n_coalesced > 1 for r in coalesced), \
        "expected at least one coalesced launch"

    for p, rs, rc in zip(reqs, serial, coalesced):
        for r in (rs, rc):
            oid, od = _oracle(p, C, r.batch_rows)
            assert np.array_equal(r.ids, oid)
            assert np.array_equal(r.dists, od)
        assert np.array_equal(rs.ids, rc.ids)
        assert np.array_equal(rs.dists, rc.dists)


def test_requests_never_split_across_launches():
    """A request's rows always come from exactly one launch (and one
    snapshot): coalescing stops before max_batch would be exceeded."""
    C = _centroids(6, 4)
    with serve({"m": C}, _quick_cfg(max_batch=32, max_linger_ms=100.0)) as srv:
        futures = [srv.submit("m", _points(20, 4, seed=i)) for i in range(3)]
        resps = [f.result(timeout=30) for f in futures]
    for r in resps:
        assert r.batch_rows <= 32
    # 20 + 20 > 32: no launch carried more than one of these requests
    assert all(r.n_coalesced == 1 for r in resps)


# ---------------------------------------------------------------------------
# recompile counter


def test_zero_recompiles_after_bucket_warmup():
    C = _centroids(10, 12)
    cfg = _quick_cfg()
    with serve({"m": C}, cfg) as srv:
        warm = srv.recompiles("m")
        assert warm == len(cfg.buckets())        # one trace per bucket
        # traffic at many distinct request sizes, serial and concurrent
        for i, m in enumerate([1, 2, 3, 5, 7, 8, 9, 15, 33, 64, 40, 12]):
            srv.assign("m", _points(m, 12, seed=i))
        futures = [srv.submit("m", _points(m, 12, seed=50 + m))
                   for m in (4, 6, 10, 14, 22)]
        for f in futures:
            f.result(timeout=30)
        assert srv.recompiles("m") == warm, \
            "serving recompiled after bucket warmup"


# ---------------------------------------------------------------------------
# hot-swap


def test_hot_swap_under_concurrent_traffic():
    """Swap mid-traffic: every request completes, each response is
    bitwise-consistent with exactly one centroid generation, and both
    generations are observed."""
    k, n = 8, 6
    C0 = _centroids(k, n, seed=1)
    perm = np.roll(np.arange(k), 1)
    C1 = C0[perm]                                # every id changes
    gens = [C0, C1]

    results: list = []
    errors: list = []
    lock = threading.Lock()

    with serve({"m": C0}, _quick_cfg(max_linger_ms=1.0,
                                     queue_depth=512)) as srv:
        stop = threading.Event()

        def client(cid: int):
            i = 0
            while not stop.is_set():
                p = _points(5 + (i % 11), n, seed=cid * 1000 + i)
                try:
                    r = srv.submit("m", p).result(timeout=30)
                except Exception as exc:          # pragma: no cover
                    with lock:
                        errors.append(exc)
                    return
                with lock:
                    results.append((p, r))
                i += 1

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        # let generation-0 traffic flow, then swap under load
        while True:
            with lock:
                if len(results) >= 20:
                    break
            time.sleep(0.005)
        srv.swap("m", C1, step=123)
        n_at_swap = len(results)
        while True:
            with lock:
                if len(results) >= n_at_swap + 20:
                    break
            time.sleep(0.005)
        stop.set()
        for t in threads:
            t.join(timeout=30)

        assert not errors
        assert ("swap", "m", 123) in srv.trace

    versions = {r.version for _, r in results}
    assert versions == {0, 1}, f"expected both generations, saw {versions}"
    for p, r in results:
        oid, od = _oracle(p, gens[r.version], r.batch_rows)
        assert np.array_equal(r.ids, oid), \
            "response mixed centroid generations"
        assert np.array_equal(r.dists, od)


def test_swap_shape_mismatch_rejected():
    C = _centroids(5, 4)
    with serve({"m": C}, _quick_cfg()) as srv:
        with pytest.raises(ValueError):
            srv.swap("m", _centroids(6, 4))
        with pytest.raises(ValueError):
            srv.swap("m", np.full((5, 4), np.nan, np.float32))
        assert srv.stats("m")["version"] == 0    # nothing swapped


def test_swap_does_not_recompile():
    C = _centroids(5, 4)
    with serve({"m": C}, _quick_cfg()) as srv:
        warm = srv.recompiles("m")
        srv.assign("m", _points(3, 4, 0))
        for i in range(3):
            srv.swap("m", _centroids(5, 4, seed=i + 10))
            srv.assign("m", _points(3, 4, seed=i))
        assert srv.recompiles("m") == warm


# ---------------------------------------------------------------------------
# tenancy


def test_multi_model_tenancy_isolation():
    """Two resident (k, n) models serve interleaved concurrent traffic;
    each response is bitwise-correct for its own model and the per-model
    accounting never bleeds across tenants."""
    Ca = _centroids(7, 5, seed=1)
    Cb = _centroids(13, 5, seed=2)
    with serve({"a": Ca, "b": Cb}, _quick_cfg(max_linger_ms=1.0)) as srv:
        futures = []
        for i in range(30):
            mid = "a" if i % 2 == 0 else "b"
            p = _points(4 + (i % 9), 5, seed=i)
            futures.append((mid, p, srv.submit(mid, p)))
        for mid, p, f in futures:
            r = f.result(timeout=30)
            assert r.model_id == mid
            oid, od = _oracle(p, Ca if mid == "a" else Cb, r.batch_rows)
            assert np.array_equal(r.ids, oid)
            assert np.array_equal(r.dists, od)
            assert r.ids.max() < (7 if mid == "a" else 13)
        stats = srv.stats()
        assert stats["a"]["n_requests"] == 15
        assert stats["b"]["n_requests"] == 15
        assert stats["a"]["k"] == 7 and stats["b"]["k"] == 13
        # swapping one tenant leaves the other untouched
        srv.swap("a", _centroids(7, 5, seed=9))
        assert srv.stats("a")["version"] == 1
        assert srv.stats("b")["version"] == 0


# ---------------------------------------------------------------------------
# admission control


def test_queue_full_rejects_immediately_not_a_hang():
    C = _centroids(5, 4)
    cfg = _quick_cfg(queue_depth=4, max_linger_ms=0.0)
    with serve({"m": C}, cfg) as srv:
        entry = srv.registry.get("m")
        in_launch = threading.Event()
        release = threading.Event()
        orig = entry.launch

        def slow_launch(q, snap):
            in_launch.set()
            release.wait(timeout=30)
            return orig(q, snap)

        entry.launch = slow_launch
        try:
            # occupy the worker, then fill the queue to queue_depth
            first = srv.submit("m", _points(2, 4, 0))
            assert in_launch.wait(timeout=10)
            queued = [srv.submit("m", _points(2, 4, i + 1)) for i in range(4)]
            t0 = time.monotonic()
            with pytest.raises(QueueFull):
                srv.submit("m", _points(2, 4, 99))
            assert time.monotonic() - t0 < 1.0, "rejection must not block"
            assert srv.stats("m")["n_rejected"] == 1
        finally:
            release.set()
            entry.launch = orig
        # the queue drains and the rejected client can retry successfully
        for f in [first] + queued:
            f.result(timeout=30)
        retry = srv.assign("m", _points(2, 4, 99))
        oid, _ = _oracle(_points(2, 4, 99), C)
        assert np.array_equal(retry.ids, oid)


def test_closed_server_rejects_and_drains():
    C = _centroids(5, 4)
    srv = serve({"m": C}, _quick_cfg())
    f = srv.submit("m", _points(3, 4, 0))
    srv.close()                                   # drains pending work
    assert f.result(timeout=30).ids.shape == (3,)
    with pytest.raises(ServerClosed):
        srv.submit("m", _points(3, 4, 1))


# ---------------------------------------------------------------------------
# checkpoint hot-swap + watcher


def _save_engine_ckpt(directory: str, step: int, centroids: np.ndarray):
    """Write a checkpoint in the engine's ((state, key), aux) layout."""
    k, n = centroids.shape
    state = bigmeans.init_state(k, n)._replace(
        centroids=jnp.asarray(centroids),
        f_best=jnp.float32(1.0))
    aux = np.asarray([0, 0, 0], dtype=np.int64)
    checkpoint.save(directory, step, ((state, jnp.zeros(2, jnp.uint32)), aux))


def test_load_centroids_verified_and_batched(tmp_path):
    d = str(tmp_path / "ckpt")
    C5 = _centroids(4, 3, seed=5)
    _save_engine_ckpt(d, 5, C5)
    got, step = load_centroids(d)
    assert step == 5 and np.array_equal(got, C5)

    # newest step torn -> verified load falls back to the intact one
    C9 = _centroids(4, 3, seed=9)
    _save_engine_ckpt(d, 9, C9)
    bad = tmp_path / "ckpt" / "step_000000000009" / "arrays.npz"
    bad.write_bytes(bad.read_bytes()[:64])
    got, step = load_centroids(d)
    assert step == 5 and np.array_equal(got, C5)

    # batched state: the best finite f_best stream is served
    B, k, n = 3, 4, 3
    Cs = np.stack([_centroids(k, n, seed=20 + b) for b in range(B)])
    state = bigmeans.init_state(k, n)._replace(
        centroids=jnp.asarray(Cs),
        f_best=jnp.asarray([np.inf, 2.0, 5.0], np.float32))
    aux = np.asarray([0, 0, 0], dtype=np.int64)
    d2 = str(tmp_path / "ckpt_b")
    checkpoint.save(d2, 1, ((state, jnp.zeros(2, jnp.uint32)), aux))
    got, _ = load_centroids(d2)
    assert np.array_equal(got, Cs[1])


def test_swap_from_checkpoint_records_step(tmp_path):
    d = str(tmp_path / "ckpt")
    C = _centroids(6, 4, seed=3)
    _save_engine_ckpt(d, 7, C)
    reg = ModelRegistry()
    reg.register("m", _centroids(6, 4, seed=0))
    snap = swap_from_checkpoint(reg, "m", d)
    assert snap.step == 7 and snap.version == 1
    assert ("swap", "m", 7) in reg.trace
    assert np.array_equal(np.asarray(snap.centroids), C)


def test_checkpoint_watcher_swaps_under_traffic(tmp_path):
    d = str(tmp_path / "ckpt")
    C0 = _centroids(5, 4, seed=0)
    C1 = _centroids(5, 4, seed=1)
    _save_engine_ckpt(d, 1, C0)
    with serve({"m": C0}, _quick_cfg()) as srv:
        watcher = srv.watch("m", d, poll_interval_s=0.02)
        time.sleep(0.1)
        assert watcher.n_swaps <= 1               # step 1 may apply once
        base = srv.stats("m")["version"]
        _save_engine_ckpt(d, 2, C1)               # "training" publishes
        deadline = time.monotonic() + 10
        while srv.stats("m")["version"] == base:
            srv.assign("m", _points(3, 4, 0))     # traffic keeps flowing
            if time.monotonic() > deadline:
                pytest.fail("watcher never swapped the new checkpoint")
            time.sleep(0.02)
        assert watcher.last_step == 2
        r = srv.assign("m", _points(3, 4, 1))
        oid, _ = _oracle(_points(3, 4, 1), C1)
        assert np.array_equal(r.ids, oid)


# ---------------------------------------------------------------------------
# kernel dispatch: serving shapes consult autotune + demotion (satellite)


@pytest.fixture
def clean_demotions():
    ops.reset_kernel_demotions()
    yield ops
    ops.reset_kernel_demotions()


def test_warm_assign_demotes_serving_shape(clean_demotions):
    """A Pallas failure at a serving shape (small m, large k) demotes that
    exact shape during warmup — the same pre-tune path fused_step gets
    from fit() — and returns the fallback impl."""
    with faults.kernel_failure("assign"):
        got = ops.warm_assign(32, 256, 16, impl="pallas_interpret")
    assert got == "ref"
    demos = ops.kernel_demotions()
    assert [d for d in demos
            if d["op"] == "assign" and d["shape"] == (1, 32, 256, 16)]
    # the demoted shape now serves through the ref path, correctly
    x = _points(32, 16, seed=0)
    c = _centroids(256, 16, seed=1)
    ids, d = ops.assign(jnp.asarray(x), jnp.asarray(c),
                        impl="pallas_interpret")
    oid, od = _oracle(x, c)
    assert np.array_equal(np.asarray(ids), oid)


def test_warm_assign_healthy_path(clean_demotions):
    assert ops.warm_assign(16, 8, 4, impl="ref") == "ref"
    assert ops.warm_assign(16, 8, 4, impl="pallas_interpret") == \
        "pallas_interpret"
    assert not ops.kernel_demotions()


def test_server_warmup_demotes_failing_pallas_end_to_end(clean_demotions):
    """Register under an injected Pallas failure: warmup demotes every
    bucket shape, and traffic then serves bitwise-correct ref results."""
    C = _centroids(10, 12)
    cfg = _quick_cfg(impl="pallas_interpret")
    with faults.kernel_failure("assign"):
        srv = serve({"m": C}, cfg)
    try:
        shapes = {d["shape"] for d in ops.kernel_demotions()
                  if d["op"] == "assign"}
        assert {(1, b, 10, 12) for b in cfg.buckets()} <= shapes
        p = _points(9, 12, seed=4)
        r = srv.assign("m", p)
        oid, od = _oracle(p, C, r.batch_rows)
        assert np.array_equal(r.ids, oid)
        assert np.array_equal(r.dists, od)
    finally:
        srv.close()
