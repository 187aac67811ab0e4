"""`repro.api` facade: config validation, data-source adapters, strategy
registry, baseline registry, deprecation shims, impl resolver.

Load-bearing guarantees:

* one ``fit(data, config, method=...)`` signature covers all four driver
  strategies AND the §5 baselines, all returning a ``FitResult``;
* ``fit(strategy='batched', batch=1)`` is fp-identical to
  ``fit(strategy='sequential')`` on the reference path (the facade preserves
  the ``test_batched.py`` equivalence);
* every ``DataSource`` adapter over the same rows serves the same chunks;
* config mistakes fail fast with actionable ``ValueError``s, not deep in a
  driver.
"""
import warnings

import jax
import numpy as np
import pytest

import repro.api as api
from repro.api import (
    ArraySource, BigMeansConfig, FitResult, IteratorSource, MemmapSource,
    ProviderSource, as_source, evaluate, fit,
)
from repro.data.synthetic import GMMSpec, gmm_chunk, gmm_dataset
from repro.kernels import ops

X = gmm_dataset(GMMSpec(m=6000, n=8, components=5, seed=33))
CFG = BigMeansConfig(k=5, s=500, n_chunks=8, impl="ref", seed=3)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [
    dict(k=0, s=100),
    dict(k=-3, s=100),
    dict(k=5, s=0),
    dict(k=50, s=10),                       # s < k
    dict(k=5, s=100, batch=0),
    dict(k=5, s=100, n_chunks=0),
    dict(k=5, s=100, sync_every=0),
    dict(k=5, s=100, tol=-1.0),
    dict(k=5, s=100, prefetch=-1),
    dict(k=5, s=100, impl="cuda"),
    dict(k=5, s=100, time_budget_s=0.0),
    dict(k=5, s=100, vns_ladder=(3,)),      # rung < k
    dict(k=5, s=100, vns_patience=0),
])
def test_config_validation_rejects(bad):
    with pytest.raises(ValueError):
        BigMeansConfig(**bad)


def test_config_replace_revalidates():
    cfg = BigMeansConfig(k=5, s=100)
    assert cfg.replace(batch=4).batch == 4
    with pytest.raises(ValueError):
        cfg.replace(s=2)


def test_fit_requires_k_and_s_without_config():
    with pytest.raises(TypeError, match="k"):
        fit(X)


def test_batched_strategy_validates_divisibility():
    with pytest.raises(ValueError, match="divide n_chunks"):
        fit(X, CFG, method="batched", batch=3)       # 3 does not divide 8
    with pytest.raises(ValueError, match="sync_every"):
        fit(X, CFG, method="batched", batch=2, sync_every=3)


def test_unknown_method_lists_options():
    with pytest.raises(KeyError, match="sequential"):
        fit(X, CFG, method="nope")


# ---------------------------------------------------------------------------
# config truth: from_workload + deprecation shims
# ---------------------------------------------------------------------------

def test_from_workload_paper_config():
    from repro.configs.bigmeans_paper import CONFIG

    cfg = BigMeansConfig.from_workload(CONFIG)
    assert (cfg.k, cfg.s) == (CONFIG.k, CONFIG.s) == (25, 64_000)
    assert cfg.n_chunks == CONFIG.chunks_per_worker
    assert cfg.batch == CONFIG.batch
    cfg2 = BigMeansConfig.from_workload(CONFIG, batch=2)
    assert cfg2.batch == 2 and CONFIG.batch == 8    # override copies


def test_workload_legacy_kwargs_deprecated():
    from repro.configs.bigmeans_paper import BigMeansWorkload

    with pytest.deprecated_call():
        wl = BigMeansWorkload(k=30, chunks_per_worker=6)
    assert wl.k == 30 and wl.algo.k == 30
    assert wl.chunks_per_worker == 6 and wl.algo.n_chunks == 6
    with pytest.raises(TypeError):
        BigMeansWorkload(bogus_knob=1)


def test_runner_config_shim():
    from repro.cluster import runner

    with pytest.deprecated_call():
        cfg = runner.RunnerConfig(k=5, s=512, batch=2)
    assert isinstance(cfg, BigMeansConfig)
    assert cfg.n_chunks == 1_000_000        # the old "until budget" default


# ---------------------------------------------------------------------------
# impl resolver (kernels/ops dispatch cache)
# ---------------------------------------------------------------------------

def test_set_default_impl_none_restores_autodetect():
    assert ops.resolve_impl("auto") == "ref"         # CPU container
    try:
        ops.set_default_impl("ref_chunked")
        assert ops.resolve_impl("auto") == "ref_chunked"
        assert ops.resolve_impl(None) == "ref_chunked"
    finally:
        ops.set_default_impl(None)
    assert ops.resolve_impl("auto") == "ref"         # cache cleared


def test_resolve_impl_validates():
    assert ops.resolve_impl("pallas_interpret") == "pallas_interpret"
    with pytest.raises(ValueError):
        ops.resolve_impl("cuda")
    with pytest.raises(ValueError):
        ops.set_default_impl("cuda")


# ---------------------------------------------------------------------------
# data sources: every adapter round-trips the same chunks
# ---------------------------------------------------------------------------

def test_array_and_memmap_sources_serve_identical_chunks(tmp_path):
    rows = np.asarray(X, dtype=np.float32)
    path = tmp_path / "data.npy"
    np.save(path, rows)

    a = ArraySource(rows)
    m = MemmapSource(path)
    assert (a.n_rows, a.n_features) == (m.n_rows, m.n_features)
    pa, pm = a.provider(64, seed=9), m.provider(64, seed=9)
    for cid in (0, 1, 17):
        ca, cm = pa(cid), pm(cid)
        assert ca.shape == cm.shape == (64, 8)
        np.testing.assert_array_equal(ca, cm)
    # same (seed, chunk_id) -> same chunk on refetch
    np.testing.assert_array_equal(pa(0), a.provider(64, seed=9)(0))


def test_provider_and_iterator_sources_round_trip():
    chunks = [np.full((16, 4), float(i), np.float32) for i in range(6)]

    psrc = ProviderSource(lambda cid: chunks[cid])
    assert psrc.n_features == 4              # probed from chunk 0
    isrc = IteratorSource(iter(chunks), n_features=4)
    pf, itf = psrc.provider(16), isrc.provider(16)
    for cid in range(6):
        np.testing.assert_array_equal(pf(cid), itf(cid))
    assert not psrc.in_core
    with pytest.raises(TypeError, match="streaming"):
        psrc.as_array()


def test_as_source_dispatch(tmp_path):
    path = tmp_path / "d.npy"
    np.save(path, np.zeros((10, 3), np.float32))
    assert isinstance(as_source(np.zeros((4, 2))), ArraySource)
    assert isinstance(as_source(X), ArraySource)
    assert isinstance(as_source(str(path)), MemmapSource)
    assert isinstance(as_source(lambda cid: None), ProviderSource)
    assert isinstance(as_source(iter([])), IteratorSource)
    src = ArraySource(np.zeros((4, 2)))
    assert as_source(src) is src
    with pytest.raises(TypeError):
        as_source(object())


def test_in_core_strategy_rejects_stream_source():
    with pytest.raises(TypeError, match="streaming"):
        fit(lambda cid: np.zeros((8, 2), np.float32), CFG,
            method="sequential", n_features=2)


# ---------------------------------------------------------------------------
# strategies: unified contract + equivalence
# ---------------------------------------------------------------------------

def _check_result(r, strategy):
    assert isinstance(r, FitResult)
    assert r.centroids.shape == (5, 8)
    assert np.isfinite(r.objective)
    assert r.strategy == strategy
    assert r.algorithm == "big_means"
    assert r.n_chunks == 8
    assert r.config.k == 5


def test_all_four_strategies_same_signature():
    key = jax.random.PRNGKey(0)
    for strategy in api.list_strategies():
        r = fit(X, CFG, method=strategy, key=key)
        _check_result(r, strategy)


def test_batched_batch1_fp_identical_to_sequential():
    key = jax.random.PRNGKey(7)
    r_seq = fit(X, CFG, method="sequential", key=key)
    r_b1 = fit(X, CFG, method="batched", key=key, batch=1)
    assert float(r_b1.objective) == float(r_seq.objective)
    np.testing.assert_array_equal(np.asarray(r_b1.centroids),
                                  np.asarray(r_seq.centroids))
    assert r_b1.n_accepted == r_seq.n_accepted
    assert r_b1.n_iterations == r_seq.n_iterations
    assert r_b1.n_dist_evals == r_seq.n_dist_evals
    assert [t[:2] for t in r_b1.trace] == [t[:2] for t in r_seq.trace]


def test_auto_strategy_resolution():
    r = fit(X, CFG)
    assert r.extras.get("auto") is True
    assert r.strategy in api.list_strategies()
    # stream-shaped source -> streaming
    assert api.resolve_auto(CFG, as_source(lambda c: None, n_features=8)) \
        == "streaming"
    # runner-only features -> streaming even for in-core data
    assert api.resolve_auto(CFG.replace(time_budget_s=60.0),
                            as_source(X)) == "streaming"
    # batch knob -> batched
    assert api.resolve_auto(CFG.replace(batch=4), as_source(X)) == "batched"


def test_streaming_strategy_from_array_source(tmp_path):
    r = fit(X, CFG, method="streaming",
            ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=4)
    _check_result(r, "streaming")
    assert r.checkpoint_dir is not None
    from repro.cluster import checkpoint
    assert checkpoint.latest_step(r.checkpoint_dir) is not None


def test_fit_registry_is_extensible():
    calls = []

    @api.register_strategy("_test_echo")
    def _echo(cfg, source, key):
        calls.append(cfg.k)
        return FitResult(centroids=np.zeros((cfg.k, source.n_features)),
                         objective=0.0, strategy="_test_echo")

    try:
        r = fit(X, CFG, method="_test_echo")
        assert calls == [5] and r.strategy == "_test_echo"
    finally:
        api.strategies._STRATEGIES.pop("_test_echo")


# ---------------------------------------------------------------------------
# baselines through the same fit()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["forgy", "kmeanspp", "coreset", "da_mssc",
                                  "ward"])
def test_baselines_same_fit_signature(name):
    r = fit(X, CFG, method=name, key=jax.random.PRNGKey(1))
    assert isinstance(r, FitResult)
    assert r.algorithm == name and r.strategy is None
    assert r.centroids.shape == (5, 8)
    assert np.isfinite(r.objective)
    _, f_full = evaluate(r, X)
    assert np.isfinite(f_full)


def test_bigmeans_competitive_with_forgy_via_facade():
    key = jax.random.PRNGKey(2)
    r_bm = fit(X, CFG, key=key)
    r_fg = fit(X, CFG, method="forgy", key=key)
    _, f_bm = evaluate(r_bm, X)
    _, f_fg = evaluate(r_fg, X)
    assert f_bm <= f_fg * 1.5


# ---------------------------------------------------------------------------
# streaming failure hygiene: fetch errors land in the trace
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefetch", [0, 2])
def test_fetch_failures_recorded_in_trace(prefetch):
    spec = GMMSpec(m=10**5, n=8, components=5, seed=3)

    def provider(cid):
        if cid == 2:
            raise RuntimeError("node lost")
        return np.asarray(gmm_chunk(spec, cid, 256))

    r = fit(provider, BigMeansConfig(k=5, s=256, n_chunks=6, seed=1,
                                     prefetch=prefetch),
            method="streaming", n_features=8)
    assert r.extras["chunks_failed"] == 1
    errors = [t for t in r.trace if t[0] == "fetch_error"]
    assert errors == [("fetch_error", 2, "RuntimeError: node lost")]


def test_iterator_exhaustion_ends_run_cleanly():
    """A finite chunk stream shorter than n_chunks is a clean end-of-stream,
    not a pile of phantom fetch failures."""
    chunks = (np.asarray(gmm_chunk(GMMSpec(m=10**4, n=8, components=5,
                                           seed=4), i, 256))
              for i in range(5))
    r = fit(chunks, BigMeansConfig(k=5, s=256, n_chunks=20, seed=0),
            method="streaming", n_features=8)
    assert r.n_chunks == 5
    assert r.extras["chunks_failed"] == 0
    assert not [t for t in r.trace if t[0] == "fetch_error"]


def test_streaming_honors_with_replacement():
    src = as_source(np.arange(40, dtype=np.float32).reshape(20, 2))
    chunk = src.provider(10, seed=0, with_replacement=False)(0)
    rows = {tuple(row) for row in chunk}
    assert len(rows) == 10                       # all rows distinct
    r = fit(src, BigMeansConfig(k=3, s=10, n_chunks=4, seed=0,
                                with_replacement=False), method="streaming")
    assert np.isfinite(r.objective)


def test_provider_probe_not_refetched():
    calls = []

    def provider(cid):
        calls.append(cid)
        return np.zeros((16, 4), np.float32) + cid

    src = as_source(provider)
    assert src.n_features == 4                   # probes chunk 0
    fetch = src.provider(16)
    np.testing.assert_array_equal(fetch(0), np.zeros((16, 4)))
    fetch(1)
    assert calls == [0, 1]                       # chunk 0 fetched exactly once


def test_auto_never_picks_invalid_sharded(monkeypatch):
    """On a multi-device host whose worker count does not divide n_chunks,
    auto must fall back instead of handing the config to a strategy that
    rejects it."""
    import repro.api.strategies as S

    monkeypatch.setattr(jax, "devices", lambda: [object()] * 3)
    cfg = CFG.replace(n_chunks=100)              # 100 % 3 != 0
    assert S.resolve_auto(cfg, as_source(X)) == "sequential"
    assert S.resolve_auto(cfg.replace(n_chunks=99), as_source(X)) == "sharded"


def test_facade_emits_no_warnings():
    """Documented usage must not trip the deprecation shims."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        fit(X, CFG, method="sequential")


def test_library_import_configures_no_compile_cache():
    """Only entry points place JAX's persistent compile cache
    (repro.launch.compile_cache.configure); importing the library leaves
    whatever the environment says."""
    import os

    import repro.api  # noqa: F401
    import repro.launch.train  # noqa: F401

    assert jax.config.jax_compilation_cache_dir == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR")
