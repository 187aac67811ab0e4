"""Batched chunk pipeline: vmap-able Lloyd, batched fused kernel, batched
driver, prefetching runner.

The load-bearing guarantees:

* ``big_means_batched(batch=1)`` IS the sequential algorithm (same key
  schedule, same incumbent trajectory);
* ``lloyd_batched`` matches B independent ``lloyd`` calls, including the
  per-stream iteration counts the paper's n_d accounting needs;
* the batched fused Pallas kernel agrees with the two-pass oracle *beyond*
  the single-chunk kernel's k<=128 / n<=1024 envelope;
* the prefetching / batched runner preserves the host-loop semantics
  (counts, failures, resume).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import BigMeansConfig
from repro.core import (
    big_means, big_means_batched, broadcast_state, chunk_step_batched,
    init_state, kmeanspp, lloyd, lloyd_batched, reduce_state,
)
from repro.core.kmeanspp import seed, seed_batched
from repro.data.synthetic import GMMSpec, gmm_dataset
from repro.kernels import ops
from repro.kernels.fused_step import (
    LEGACY_MAX_K, LEGACY_MAX_N, MAX_N, fits, fits_batched, max_k,
    fused_step_batched_pallas,
)

X = gmm_dataset(GMMSpec(m=8000, n=8, components=5, seed=21))


# ---------------------------------------------------------------------------
# lloyd: masked iteration, vmap-ability, explicit batching
# ---------------------------------------------------------------------------

def _stream_data(B, s, k, key=0):
    kx = jax.random.split(jax.random.PRNGKey(key), B)
    pts = jnp.stack([X[i * s:(i + 1) * s] for i in range(B)])
    cs = jnp.stack([kmeanspp(pts[i], kx[i], k) for i in range(B)])
    return pts, cs


def test_lloyd_batched_matches_independent_runs():
    B, s, k = 3, 1000, 5
    pts, cs = _stream_data(B, s, k)
    rb = lloyd_batched(pts, cs, impl="ref")
    for i in range(B):
        ri = lloyd(pts[i], cs[i], impl="ref")
        np.testing.assert_allclose(
            float(rb.objective[i]), float(ri.objective), rtol=1e-5)
        assert int(rb.iterations[i]) == int(ri.iterations)
        np.testing.assert_allclose(
            np.asarray(rb.centroids[i]), np.asarray(ri.centroids),
            rtol=1e-4, atol=1e-4)
        np.testing.assert_array_equal(
            np.asarray(rb.counts[i]), np.asarray(ri.counts))


def test_lloyd_is_vmappable():
    """The masked-iteration scheme makes plain `lloyd` vmap-able: converged
    streams become no-ops instead of breaking the while_loop."""
    B, s, k = 3, 800, 4
    pts, cs = _stream_data(B, s, k, key=1)
    rv = jax.vmap(lambda p, c: lloyd(p, c, impl="ref"))(pts, cs)
    for i in range(B):
        ri = lloyd(pts[i], cs[i], impl="ref")
        np.testing.assert_allclose(
            float(rv.objective[i]), float(ri.objective), rtol=1e-5)
        assert int(rv.iterations[i]) == int(ri.iterations)


def test_lloyd_batched_respects_max_iters():
    B, s, k = 2, 500, 4
    pts, cs = _stream_data(B, s, k, key=2)
    rb = lloyd_batched(pts, cs, max_iters=3, tol=0.0, impl="ref")
    assert int(rb.iterations.max()) <= 3


# ---------------------------------------------------------------------------
# batched fused kernel: parity beyond the single-chunk envelope
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,m,n,k", [
    (2, 300, 28, 25),        # paper regime
    (3, 257, 64, 128),       # ragged m tile, envelope edge
    (1, 400, 20, 200),       # k > 128: beyond the single-chunk wall
    (2, 300, 1100, 40),      # n > 1024: beyond the single-chunk wall
    (1, 200, 1500, 256),     # both walls at once
])
def test_batched_fused_kernel_matches_two_pass(B, m, n, k):
    kx, kc = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (B, m, n))
    c = jax.random.normal(kc, (B, k, n))
    assert fits_batched(k, n)
    if k > LEGACY_MAX_K or n > LEGACY_MAX_N:
        # Beyond the historical single-chunk envelope — the k-tiled argmin
        # rewrite widened fits() to cover these shapes in one kernel too.
        assert fits(k, n)
    assert fits(max_k(n), n)
    assert not fits(max_k(n) + 1, n)     # the widened wall still exists
    assert not fits(k, MAX_N + 1)
    s_p, n_p, o_p = fused_step_batched_pallas(x, c, interpret=True)
    s_r, n_r, o_r = ops._fused_step_batched_ref(x, c)
    np.testing.assert_allclose(np.asarray(n_p), np.asarray(n_r), atol=0)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r),
                               rtol=2e-3, atol=2e-2)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), rtol=2e-3)


def test_fused_step_batched_dispatch():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 200, 16))
    c = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 16))
    s1, n1, o1 = ops.fused_step_batched(x, c, impl="ref")
    s2, n2, o2 = ops.fused_step_batched(x, c, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2))
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5)


# ---------------------------------------------------------------------------
# vmap-safe seeding
# ---------------------------------------------------------------------------

def test_seed_batched_matches_per_stream():
    B, s, k = 3, 1000, 5
    pts = jnp.stack([X[i * s:(i + 1) * s] for i in range(B)])
    keys = jax.random.split(jax.random.PRNGKey(3), B)
    init = jnp.stack([pts[i, :k] for i in range(B)])
    deg = jnp.array([[False, True, False, True, False]] * B)
    out = seed_batched(pts, keys, k, init=init, degenerate=deg[0] * deg)
    for i in range(B):
        ref_i = seed(pts[i], keys[i], k, init=init[i],
                     degenerate=(deg[0] * deg)[i])
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(ref_i))


# ---------------------------------------------------------------------------
# batched driver: batch=1 equivalence, stream sync, state algebra
# ---------------------------------------------------------------------------

def test_big_means_batched_batch1_equals_sequential():
    key = jax.random.PRNGKey(7)
    st_seq, inf_seq = big_means(X, key, k=5, s=600, n_chunks=12, impl="ref")
    st_b1, inf_b1 = big_means_batched(
        X, key, k=5, s=600, batch=1, rounds=12, impl="ref")
    np.testing.assert_allclose(
        float(st_b1.f_best), float(st_seq.f_best), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(st_b1.centroids), np.asarray(st_seq.centroids),
        rtol=1e-5, atol=1e-5)
    assert int(st_b1.n_accepted) == int(st_seq.n_accepted)
    np.testing.assert_allclose(
        float(st_b1.n_dist_evals), float(st_seq.n_dist_evals), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(inf_b1.f_new), np.asarray(inf_seq.f_new), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(inf_b1.accepted), np.asarray(inf_seq.accepted))


@pytest.mark.parametrize("sync_every", [1, 3])
def test_big_means_batched_multi_stream(sync_every):
    key = jax.random.PRNGKey(8)
    st, infos = big_means_batched(
        X, key, k=5, s=600, batch=4, rounds=6, sync_every=sync_every,
        impl="ref")
    assert infos.f_new.shape == (24,)
    assert np.isfinite(float(st.f_best))
    assert int(st.n_accepted) >= 1
    # the reduced incumbent is at least as good as every observed chunk f
    assert float(st.f_best) <= float(np.min(np.asarray(infos.f_new))) + 1e-3


def test_big_means_batched_quality_tracks_sequential():
    from repro.core import full_objective
    key = jax.random.PRNGKey(9)
    st_b, _ = big_means_batched(X, key, k=5, s=600, batch=4, rounds=6,
                                impl="ref")
    st_s, _ = big_means(X, key, k=5, s=600, n_chunks=24, impl="ref")
    f_b = float(full_objective(X, st_b.centroids)) / X.shape[0]
    f_s = float(full_objective(X, st_s.centroids)) / X.shape[0]
    assert f_b <= f_s * 1.15


def test_broadcast_reduce_state_roundtrip():
    state = init_state(4, 8)._replace(
        centroids=jnp.ones((4, 8)), degenerate=jnp.zeros((4,), bool),
        f_best=jnp.float32(5.0), n_accepted=jnp.int32(3),
        n_dist_evals=jnp.float32(100.0))
    bs = broadcast_state(state, 3)
    assert bs.centroids.shape == (3, 4, 8)
    assert int(jnp.sum(bs.n_accepted)) == 0      # counters zeroed per stream
    # pretend stream 1 improved
    bs = bs._replace(
        f_best=bs.f_best.at[1].set(2.0),
        n_accepted=bs.n_accepted.at[1].set(1),
        n_dist_evals=bs.n_dist_evals + 10.0)
    red = reduce_state(bs, base=state)
    assert float(red.f_best) == 2.0
    assert int(red.n_accepted) == 3 + 1
    assert float(red.n_dist_evals) == 100.0 + 30.0


def test_chunk_step_batched_keeps_best_per_stream():
    B, s, k = 3, 500, 5
    pts = jnp.stack([X[i * s:(i + 1) * s] for i in range(B)])
    keys = jax.random.split(jax.random.PRNGKey(10), B)
    states = broadcast_state(init_state(k, 8), B)
    states, info = chunk_step_batched(pts, states, keys, impl="ref")
    assert bool(jnp.all(info.accepted))          # first chunk always accepted
    np.testing.assert_allclose(
        np.asarray(states.f_best), np.asarray(info.f_new), rtol=1e-6)


# ---------------------------------------------------------------------------
# prefetching / batched runner
# ---------------------------------------------------------------------------

def _provider_spec():
    from repro.data.synthetic import gmm_chunk
    spec = GMMSpec(m=10**6, n=8, components=5, seed=3)

    def provider(cid):
        return np.asarray(gmm_chunk(spec, cid, 512))

    return provider


def test_runner_batched_end_to_end():
    from repro.cluster import runner
    provider = _provider_spec()
    cfg = BigMeansConfig(k=5, s=512, n_chunks=12, batch=4, seed=1)
    state, m = runner.run(provider, cfg, n_features=8)
    assert m.chunks_done == 12
    assert np.isfinite(m.f_best)


def test_runner_batched_partial_batch_and_failures():
    from repro.cluster import runner
    provider = _provider_spec()

    def bomb(cid):
        if cid in (2, 5):
            raise RuntimeError("node lost")

    cfg = BigMeansConfig(k=5, s=512, n_chunks=11, batch=4, seed=2)
    state, m = runner.run(provider, cfg, n_features=8, fault_injector=bomb)
    assert m.chunks_failed == 2
    assert m.chunks_done == 9          # 2 full batches + partial final batch


def test_runner_prefetch_matches_sync():
    """The prefetch thread must not change results: chunk keys are folded
    from ids, so pipelined and synchronous fetch produce identical runs."""
    from repro.cluster import runner
    provider = _provider_spec()
    cfg_pre = BigMeansConfig(k=5, s=512, n_chunks=8, prefetch=3, seed=4)
    cfg_syn = BigMeansConfig(k=5, s=512, n_chunks=8, prefetch=0, seed=4)
    st_p, m_p = runner.run(provider, cfg_pre, n_features=8)
    st_s, m_s = runner.run(provider, cfg_syn, n_features=8)
    assert m_p.chunks_done == m_s.chunks_done == 8
    np.testing.assert_allclose(m_p.f_best, m_s.f_best, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(st_p.centroids), np.asarray(st_s.centroids),
        rtol=1e-5, atol=1e-5)
