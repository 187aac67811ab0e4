"""k in the thousands: the fused Lloyd kernel past k = 1024 (lane tiles
that widen with k), its exact one-hot update, K-means++ seeding at such a
k, and the ``seed_steps`` counter."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import BigMeansConfig, fit
from repro.core.bigmeans import chunk_step, chunk_step_batched, init_state
from repro.kernels import autotune, ops
from repro.kernels import fused_step as fused
from repro.kernels import precision as px
from repro.kernels.ref import pairwise_sqdist_ref

kpp = importlib.import_module("repro.core.kmeanspp")


def _separated(B, m, n, k, seed):
    """Points around k well-separated centres; centroids near the centres."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 10, (B, k, n)).astype(np.float32)
    label = rng.integers(0, k, (B, m))
    x = np.take_along_axis(centers, label[..., None], 1) \
        + rng.normal(0, 1, (B, m, n)).astype(np.float32)
    c = centers + rng.normal(0, 0.01, centers.shape).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(c)


def _assert_matches(got, want):
    (s_p, n_p, o_p), (s_r, n_r, o_r) = got, want
    np.testing.assert_array_equal(np.asarray(n_p), np.asarray(n_r))
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_r), rtol=1e-5)


# ---------------------------------------------------------------------------
# the fused kernel past k = 1024
# ---------------------------------------------------------------------------

LARGE = [(1152, 28), (1152, 128), (4096, 28), (4096, 128)]


@pytest.mark.parametrize("k,n", LARGE)
def test_batched_kernel_past_1024_matches_the_oracle(k, n):
    x, c = _separated(2, 512, n, k, seed=k + n)
    assert fused.fits_batched(k, n)
    assert 1 < fused.k_tiles(k) <= fused.UNROLL_K_TILES
    _assert_matches(fused.fused_step_batched_pallas(x, c, interpret=True),
                    ops._fused_step_batched_ref(x, c))


@pytest.mark.parametrize("pipeline", ["blocks", "dma"])
@pytest.mark.parametrize("k,n", LARGE)
def test_single_kernel_past_1024_matches_the_oracle(k, n, pipeline):
    x, c = _separated(1, 512, n, k, seed=k + n)
    x, c = x[0], c[0]
    ids, d = ops.assign(x, c, impl="ref")
    sums, counts = ops.update(x, ids, k, impl="ref")
    _assert_matches(
        fused.fused_step_pallas(x, c, pipeline=pipeline, interpret=True),
        (sums, counts, jnp.sum(d)))


@pytest.mark.parametrize("k,block_k", [
    (1, 128), (25, 128), (1000, 128), (1024, 128), (1025, 256),
    (1152, 256), (2048, 256), (4096, 512), (5000, 640), (8192, 1024)])
def test_the_default_lane_tile_covers_k_in_at_most_8_tiles(k, block_k):
    k_pad, _, bk, _ = fused._batched_tiles(k, 128)
    assert bk == block_k                  # k <= 1024: the 128 of before
    assert fused.k_tiles(k) == k_pad // bk <= fused.UNROLL_K_TILES
    assert k_pad - k < bk


@pytest.mark.parametrize("k,onehot", [
    (25, False), (1024, False), (1025, True), (4096, True)])
def test_only_k_past_1024_takes_the_onehot_update(k, onehot):
    """Under f32 every k <= 1024 keeps the policy dot (no bf16 in the
    kernel); past it the update contracts the point tile's bf16 parts."""
    x = jax.ShapeDtypeStruct((2, 256, 128), jnp.float32)
    c = jax.ShapeDtypeStruct((2, k, 128), jnp.float32)
    step = functools.partial(fused.fused_step_batched_pallas, interpret=True)
    assert ("bf16" in str(jax.make_jaxpr(step)(x, c))) == onehot


@pytest.mark.parametrize("shape", [(300, 17, 40), (256, 512, 128)])
@pytest.mark.parametrize("precision", ["f32", "bf16", "bf16x3"])
def test_the_onehot_update_is_the_policy_dot(shape, precision):
    """Three bf16 passes under f32: each product exact, so the sums are
    the f32 dot's up to f32 accumulation; other policies are px.dot."""
    m, k, n = shape
    rng = np.random.default_rng(m + k)
    b = jnp.asarray(rng.normal(0, 3, (m, n)).astype(np.float32)
                    * np.float32(2.0) ** rng.integers(-20, 20, (m, n)))
    hit = jnp.asarray(rng.random((m, k)) < 0.2)
    dn = (((0,), (0,)), ((), ()))
    got = np.asarray(px.onehot_dot(hit, b, dn, precision))
    want = np.asarray(px.dot(hit.astype(jnp.float32), b, dn, precision))
    if precision != "f32":
        np.testing.assert_array_equal(got, want)
        return
    hi, mid = px._split_bf16(b)
    lo = (b - hi.astype(jnp.float32) - mid.astype(jnp.float32))
    assert bool(jnp.all(lo.astype(jnp.bfloat16).astype(jnp.float32) == lo))
    exact = np.asarray(hit, np.float64).T @ np.asarray(b, np.float64)
    scale = np.asarray(hit, np.float64).T @ np.abs(np.asarray(b, np.float64))
    # f32 summation of m exact products, then of the three parts
    bound = (m + 2) * 2.0 ** -24
    assert np.max(np.abs(got - exact) / np.maximum(scale, 1e-30)) < bound


def test_the_envelope_is_the_resident_vmem():
    # every shape the k <= 1024 envelope admitted is still inside
    for k in (1, 25, 128, 1000, 1024):
        for n in (8, 28, 768, 1024, 4096):
            k_pad, n_pad, _, _ = fused._batched_tiles(k, n)
            if k_pad * n_pad <= 1 << 20:
                assert fused.fits(k, n), (k, n)
    assert fused.fits(4096, 128) and fused.fits(8192, 128)
    assert fused.fits(4096, 256) and not fused.fits(8192, 256)
    assert fused.resident_bytes(4096, 128) == 8 * 2**20
    assert [fused.max_k(n) for n in (28, 128, 256, 1024, 4096, 4097)] \
        == [8192, 8192, 4096, 1024, 256, 0]


@pytest.mark.parametrize("kind", ["fused", "fused_batched"])
def test_autotune_candidates_are_valid_at_k4096(kind):
    cands = autotune.candidates(kind, b=8, m=64000, k=4096, n=128,
                                precision="f32")
    assert cands[0]["block_k"] == 512 and cands[0]["block_n"] == 128
    assert len(cands) > 1
    for blk in cands:
        assert fused.fits_tiling(4096, 128, blk["block_k"], blk["block_n"])
        assert fused.k_tiles(4096, blk["block_k"]) <= fused.UNROLL_K_TILES


def test_a_fit_past_1024_runs_the_fused_kernel_and_matches_ref():
    """k = 1152 (5 lane tiles of 256) through fit(method='batched'): no
    demotion, the ref fit's answer."""
    rng = np.random.default_rng(0)
    centers = rng.normal(0, 10, (1152, 8)).astype(np.float32)
    X = centers[rng.integers(0, 1152, 6000)] \
        + rng.normal(0, 0.5, (6000, 8)).astype(np.float32)
    cfg = BigMeansConfig(k=1152, s=1536, n_chunks=2, batch=2, max_iters=3,
                         seed=4)
    ops.reset_kernel_demotions()
    try:
        got = fit(X, cfg, method="batched", impl="pallas_interpret")
        assert ops.kernel_demotions() == []
    finally:
        ops.reset_kernel_demotions()
    want = fit(X, cfg, method="batched", impl="ref")
    assert got.n_iterations == want.n_iterations
    np.testing.assert_allclose(got.objective, want.objective, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got.centroids),
                               np.asarray(want.centroids),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# K-means++ seeding
# ---------------------------------------------------------------------------


_BIG = np.float32(1e30)


def _seed_as_before(points, key, k, init, degenerate, candidates=3):
    """The seeding loop written out for one stream: the full [s, k]
    distance matrix, then its row minimum, then the D^2 loop."""
    points = points.astype(jnp.float32)
    x2 = jnp.sum(jnp.square(points), axis=-1, keepdims=True)
    d_all = pairwise_sqdist_ref(points, init, x2)
    d_all = jnp.where(degenerate[None, :], _BIG, d_all)
    d0 = jnp.minimum(jnp.min(d_all, axis=1), _BIG)

    def body(j, carry):
        key, c, d = carry
        key, k1 = jax.random.split(key)
        logits = kpp._safe_d2_logits(d)
        cand_idx = jax.random.categorical(k1, logits, shape=(candidates,))
        cands = points[cand_idx]
        newd = jnp.minimum(d[:, None], pairwise_sqdist_ref(points, cands, x2))
        b = jnp.argmin(jnp.sum(newd, axis=0))
        is_deg = degenerate[j]
        chosen = jnp.where(is_deg, cands[b], c[j])
        d = jnp.where(is_deg, newd[:, b], d)
        return key, c.at[j].set(chosen), d

    return jax.lax.fori_loop(0, k, body, (key, init, d0))[1]


def _integer_points(B, s, n, k, seed):
    """Small integers: every distance is exact in f32, whatever the order."""
    rng = np.random.default_rng(seed)
    pts = rng.integers(-8, 9, (B, s, n)).astype(np.float32)
    init = rng.integers(-8, 9, (B, k, n)).astype(np.float32)
    return jnp.asarray(pts), jnp.asarray(init)


def test_fresh_seeding_is_bit_identical_to_the_full_matrix_loop():
    rng = np.random.default_rng(1)
    B, s, n, k = 3, 700, 12, 300
    pts = jnp.asarray(rng.normal(0, 3, (B, s, n)).astype(np.float32))
    keys = jax.random.split(jax.random.PRNGKey(2), B)
    init = jnp.zeros((B, k, n), jnp.float32)
    deg = jnp.ones((B, k), bool)
    want = jnp.stack([_seed_as_before(pts[b], keys[b], k, init[b], deg[b])
                      for b in range(B)])
    got = kpp.seed_batched(pts, keys, k, init=init, degenerate=deg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(kpp.kmeanspp(pts[0], keys[0], k)), np.asarray(want[0]))
    np.testing.assert_array_equal(
        np.asarray(kpp.seed(pts[1], keys[1], k, init=init[1],
                            degenerate=deg[1])), np.asarray(want[1]))


def test_partial_reseeding_matches_the_full_matrix_loop():
    """Masks that keep some slots, one stream fully degenerate: on integer
    data every distance is exact, so the batched seeds are the same bits."""
    B, s, n, k = 3, 600, 10, 600
    pts, init = _integer_points(B, s, n, k, seed=5)
    rng = np.random.default_rng(6)
    deg = jnp.asarray(np.stack([rng.random(k) < 0.3, np.ones(k, bool),
                                rng.random(k) < 0.8]))
    keys = jax.random.split(jax.random.PRNGKey(7), B)
    want = jnp.stack([_seed_as_before(pts[b], keys[b], k, init[b], deg[b])
                      for b in range(B)])
    got = kpp.seed_batched(pts, keys, k, init=init, degenerate=deg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_seed_steps_count_the_d2_steps_that_ran():
    rng = np.random.default_rng(2)
    B, s, n, k = 2, 400, 6, 5
    pts = jnp.asarray(rng.normal(size=(B, s, n)).astype(np.float32))
    keys = jax.random.split(jax.random.PRNGKey(0), B)
    fresh = jax.tree.map(lambda a: jnp.broadcast_to(a, (B,) + a.shape),
                         init_state(k, n))
    kw = dict(max_iters=4, impl="ref")
    _, info = chunk_step_batched(pts, fresh, keys, **kw)
    np.testing.assert_array_equal(np.asarray(info.seed_steps), [k, k])
    settled = fresh._replace(degenerate=jnp.zeros((B, k), bool),
                             centroids=pts[:, :k])
    _, info = chunk_step_batched(pts, settled, keys, **kw)
    np.testing.assert_array_equal(np.asarray(info.seed_steps), [0, 0])
    one = settled._replace(degenerate=settled.degenerate.at[1, 2].set(True))
    _, info = chunk_step_batched(pts, one, keys, **kw)
    np.testing.assert_array_equal(np.asarray(info.seed_steps), [k, k])
    _, info = chunk_step(pts[0], init_state(k, n), keys[0], **kw)
    assert int(info.seed_steps) == k


@pytest.mark.parametrize("method", ["batched", "sequential"])
def test_fit_reports_its_seed_steps(method):
    """Four far-apart blobs and k = 4: the first chunk of each stream
    seeds, and no later chunk finds an empty slot to re-seed."""
    rng = np.random.default_rng(3)
    centers = np.array([[0, 0], [50, 0], [0, 50], [50, 50]], np.float32)
    X = centers[rng.integers(0, 4, 4000)] \
        + rng.normal(0, 1, (4000, 2)).astype(np.float32)
    cfg = BigMeansConfig(k=4, s=500, n_chunks=6, batch=2, impl="ref")
    r = fit(X, cfg, method=method)
    seeded = 2 if method == "batched" else 1      # the first round's chunks
    assert r.seed_steps == 4 * seeded
    assert r.to_row()["seed_steps"] == r.seed_steps
