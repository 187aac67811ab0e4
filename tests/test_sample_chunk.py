"""Chunk rows gathered from the point-major packed copy of a lane-sparse
dataset: the same rows as ``jnp.take`` on the dataset, the same fits, and
the plan that chooses between the two gathers."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import BigMeansConfig, strategies
from repro.api.sources import as_source
from repro.core.bigmeans import LANES, pack_rows, packed_width, sample_chunk
from repro.engine import incore

M = 4099          # a multiple of no packed group size g = 128 // w


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _data(m, n, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, n)).astype(dtype)


@pytest.mark.parametrize("with_replacement", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [3, 28, 68, 100])
def test_the_packed_gather_returns_exactly_the_rows_take_returns(
        n, dtype, with_replacement):
    X = _data(M, n, seed=n, dtype=dtype)
    key = jax.random.PRNGKey(5)
    want = sample_chunk(X, key, 1000, with_replacement=with_replacement)
    got = jax.jit(lambda x, k: sample_chunk(
        x, k, 1000, with_replacement=with_replacement,
        packed=pack_rows(x)))(X, key)
    assert got.dtype == X.dtype and got.shape == (1000, n)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("n, w", [(1, 1), (3, 4), (28, 32), (64, 64),
                                  (68, 128), (127, 128)])
def test_the_packed_copy_holds_each_point_in_one_lane_dense_row(n, w):
    assert packed_width(n) == w
    X = _data(M, n)
    packed = pack_rows(X)
    rows, g = packed.shape[0], LANES // w
    assert packed.shape[1] == LANES and rows % LANES == 0
    assert rows * g >= M > (rows - LANES) * g
    # point i: row i % rows, lanes (i // rows) * w onward; zeros elsewhere
    i = np.arange(M)
    lanes = (i // rows)[:, None] * w + np.arange(n)
    np.testing.assert_array_equal(np.asarray(packed)[i[:, None] % rows,
                                                     lanes], np.asarray(X))
    assert float(jnp.sum(jnp.abs(packed))) == pytest.approx(
        float(jnp.sum(jnp.abs(X))), rel=1e-5)


def test_a_lane_dense_width_keeps_the_rows_gather():
    X = _data(3000, 768)
    assert packed_width(768) is None
    assert incore.gather_for(X, "auto", 8 * 300) == "rows"
    text = incore.sequential.lower(
        X, jax.random.PRNGKey(0), k=3, s=300, n_chunks=2, impl="ref"
    ).compile().as_text()
    assert "slice_sizes={1,768}" in text
    assert f"slice_sizes={{1,{LANES}}}" not in text


# ---------------------------------------------------------------------------
# the plan's choice
# ---------------------------------------------------------------------------


def _device(bytes_limit):
    return types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": bytes_limit,
                              "bytes_in_use": 0})


def test_a_lane_sparse_dataset_that_fits_is_gathered_packed(monkeypatch):
    X = np.zeros((10_000, 28), np.float32)
    # no bytes_limit on this backend: nothing to check against
    assert incore.gather_for(X, "f32", 8 * 1000) == "packed"
    # 10,000 x 28 f32 is 1.12 MB; its copy 2 x 2560 x 128 x 4 = 2.62 MB
    # (the copy and the intermediate it is built through); 8000 packed
    # rows gathered 4.10 MB: 7.84 MB in all
    monkeypatch.setattr(jax, "devices", lambda *a: [_device(8_000_000)])
    assert incore.gather_for(X, "f32", 8 * 1000) == "packed"
    # bf16 storage halves every term
    monkeypatch.setattr(jax, "devices", lambda *a: [_device(4_000_000)])
    assert incore.gather_for(X, "bf16", 8 * 1000) == "packed"
    assert incore.gather_for(X, "f32", 8 * 1000) == "rows"


def test_a_dataset_whose_packed_copy_does_not_fit_is_gathered_by_rows(
        monkeypatch):
    X = np.zeros((10_000, 28), np.float32)
    monkeypatch.setattr(jax, "devices", lambda *a: [_device(7_000_000)])
    assert incore.gather_for(X, "f32", 8 * 1000) == "rows"
    # four workers each hold and pack a quarter of the rows
    assert incore.gather_for(X, "f32", 1000, shards=4) == "packed"


@pytest.mark.parametrize("n, gather", [(28, "packed"), (768, "rows")])
@pytest.mark.parametrize("method", ["sequential", "batched"])
def test_the_planned_program_takes_the_plans_choice(method, n, gather):
    X = _data(2000, n)
    cfg = BigMeansConfig(k=3, s=200, n_chunks=4, batch=2, impl="ref")
    program = strategies.plan(method, cfg, as_source(X),
                              jax.random.PRNGKey(0))
    assert program.kwargs["gather"] == gather
    assert program.dispatch_args()["gather"] == gather


# ---------------------------------------------------------------------------
# whole fits: the same result from either gather
# ---------------------------------------------------------------------------


def _fit_with(gather, X, method, impl):
    """``fit()``'s planned program with its gather forced, and the
    :class:`FitResult` it collects."""
    cfg = BigMeansConfig(k=4, s=400, n_chunks=8, batch=4, sync_every=2,
                         impl=impl, max_iters=20)
    program = strategies.plan(method, cfg, as_source(X),
                              jax.random.PRNGKey(11))
    program = program._replace(kwargs=dict(program.kwargs, gather=gather))
    return strategies.collect(program, program.dispatch())


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("method", ["sequential", "batched"])
@pytest.mark.parametrize("n", [3, 28])
def test_a_fit_is_the_same_from_either_gather(n, method, impl):
    X = _data(5000, n, seed=3) * 4.0
    rows = _fit_with("rows", X, method, impl)
    packed = _fit_with("packed", X, method, impl)
    np.testing.assert_array_equal(_bits(packed.centroids),
                                  _bits(rows.centroids))
    assert packed.objective == rows.objective
    assert packed.trace == rows.trace
    assert packed.n_iterations == rows.n_iterations
