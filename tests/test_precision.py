"""Mixed-precision kernel stack + block-size autotuner.

Covers the ISSUE 3 acceptance matrix:

* bf16 numerics: objective within rtol=1e-2 of the f32 oracle; batch=1
  batched kernel == single kernel under bf16; padding (lanes and features)
  never wins an argmin or leaks into sums.
* ``fit(..., precision='bf16')`` within 1% relative ``f_best`` of the f32
  run on the paper-regime synthetic workload (same seeds).
* autotuner: tile choice never changes numerics; on-disk cache write +
  reload round-trip; ops consults the tuner under ``pallas_interpret``.
* ``ops.fused_step`` two-pass fallback honors ``impl='ref_chunked'``.

And the ISSUE 9 kernel-depth matrix:

* int8 numerics: bitwise ref-vs-Pallas parity on integer data; padding
  invariance; ``fit(..., precision='int8')`` within 1% of f32 on the
  evalsuite quick datasets; ``warm_assign`` demotes the int8 serving
  shape under injected kernel failure.
* k > 128 argmin tiling: a k=256 shape (legacy envelope miss) runs the
  single fused kernel and matches the two-pass oracle; the autotuner's
  candidate set covers it.
* double-buffered DMA pipeline: 'dma' matches 'blocks' bitwise on
  integer data and both are autotune candidates.
* committed profile round-trip: ``results/autotune/interpret.json``
  loads, is consulted by ops, and corrupt / stale-schema cache files are
  ignored with a recorded event instead of crashing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref
from repro.kernels import precision as px
from repro.kernels.fused_step import fused_step_batched_pallas, fused_step_pallas


def _blobs(m=400, n=28, k=25, seed=0):
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    centers = jax.random.normal(kc, (k, n)) * 4.0
    ids = jax.random.randint(jax.random.PRNGKey(seed + 1), (m,), 0, k)
    x = centers[ids] + jax.random.normal(kx, (m, n)) * 0.3
    c = centers + 0.05
    return x, c


# ---------------------------------------------------------------------------
# precision policy helpers
# ---------------------------------------------------------------------------


def test_precision_validation():
    with pytest.raises(ValueError, match="unknown precision"):
        px.check("fp8")
    assert px.check("bf16") == "bf16"
    assert px.storage_dtype("bf16") == jnp.bfloat16
    assert px.storage_dtype("bf16x3") == jnp.float32
    # 'auto' follows the data dtype (legacy behaviour); concrete values win
    assert px.resolve("auto", jnp.bfloat16) == "bf16"
    assert px.resolve(None, jnp.float32) == "f32"
    assert px.resolve("f32", jnp.bfloat16) == "f32"


def test_bf16x3_compensation_beats_bf16():
    x, c = _blobs()
    d32 = ref.pairwise_sqdist_ref(x, c, precision="f32")
    dbf = ref.pairwise_sqdist_ref(x, c, precision="bf16")
    dx3 = ref.pairwise_sqdist_ref(x, c, precision="bf16x3")
    err_bf = float(jnp.max(jnp.abs(dbf - d32)))
    err_x3 = float(jnp.max(jnp.abs(dx3 - d32)))
    assert err_x3 < err_bf / 4, (err_x3, err_bf)


# ---------------------------------------------------------------------------
# bf16 kernel numerics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,n,k", [(300, 28, 25), (257, 100, 37)])
def test_bf16_fused_objective_close_to_f32_oracle(m, n, k):
    # Unit-scale blobs: the per-iteration kernel objective carries raw bf16
    # dot rounding (the compensated f32 epilogue is lloyd's, tested below),
    # so the comparison runs where distances are not cancellation-dominated.
    kx, kc = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (m, n))
    c = jax.random.normal(kc, (k, n))
    _, _, obj_bf = fused_step_pallas(x, c, precision="bf16", interpret=True)
    ids, d = ref.assign_ref(x, c, precision="f32")
    obj_f32 = float(jnp.sum(d))
    np.testing.assert_allclose(float(obj_bf), obj_f32, rtol=1e-2)


def test_bf16_lloyd_objective_close_to_f32_oracle():
    from repro.core import kmeans
    from repro.core.kmeanspp import kmeanspp

    x, _ = _blobs(m=2000, n=12, k=6, seed=7)
    c0 = kmeanspp(x, jax.random.PRNGKey(5), 6)
    res32 = kmeans.lloyd(x, c0, impl="ref", precision="f32")
    resbf = kmeans.lloyd(x, c0, impl="ref", precision="bf16")
    np.testing.assert_allclose(float(resbf.objective), float(res32.objective),
                               rtol=1e-2)


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_batched_batch1_matches_single_kernel(precision):
    x, c = _blobs(m=300, n=28, k=25)
    s1, n1, o1 = fused_step_pallas(x, c, precision=precision, interpret=True)
    sb, nb, ob = fused_step_batched_pallas(
        x[None], c[None], precision=precision, interpret=True)
    np.testing.assert_array_equal(np.asarray(nb[0]), np.asarray(n1))
    np.testing.assert_allclose(np.asarray(sb[0]), np.asarray(s1),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(float(ob[0]), float(o1), rtol=1e-6)


@pytest.mark.parametrize("m,n,k", [(257, 29, 5), (100, 130, 129)])
def test_bf16_padding_invariance(m, n, k):
    """Padded lanes must never win an argmin; padded features never leak."""
    x, c = _blobs(m, n, k, seed=3)
    ids, d = ops.assign(x, c, impl="pallas_interpret", precision="bf16")
    assert int(jnp.max(ids)) < k
    assert int(jnp.min(ids)) >= 0
    assert bool(jnp.all(d >= 0))
    # same inputs embedded in a larger feature space padded with zeros:
    # distances and assignments are unchanged (bf16 zero-padding is exact)
    ids_ref, d_ref = ref.assign_ref(
        x.astype(jnp.bfloat16), c, precision="bf16")
    agree = np.mean(np.asarray(ids) == np.asarray(ids_ref))
    assert agree > 0.99, agree
    sums, counts = ops.update(x, ids, k, impl="pallas_interpret",
                              precision="bf16")
    assert float(jnp.sum(counts)) == m
    # zero-feature padding in the kernel cannot contribute to sums: compare
    # against the oracle over identical assignments
    sums_ref, counts_ref = ref.update_ref(x.astype(jnp.bfloat16), ids, k,
                                          precision="bf16")
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_ref))
    np.testing.assert_allclose(np.asarray(sums), np.asarray(sums_ref),
                               rtol=1e-2, atol=1e-2)


def test_fit_bf16_within_1pct_of_f32():
    """Acceptance: paper-regime synthetic workload, same seeds, <1% f_best."""
    from repro.api import BigMeansConfig, fit, synthetic

    X = synthetic.gmm_dataset(
        synthetic.GMMSpec(m=60_000, n=20, components=25, seed=12))
    cfg = BigMeansConfig(k=25, s=8192, n_chunks=8, impl="ref", seed=0)
    r32 = fit(X, cfg)
    rbf = fit(X, cfg, precision="bf16")
    rel = abs(rbf.objective - r32.objective) / r32.objective
    assert rel < 0.01, (r32.objective, rbf.objective, rel)


def test_streaming_runner_serves_bf16_chunks():
    from repro.api import BigMeansConfig, as_source, fit

    rng = np.random.default_rng(0)
    X = rng.normal(size=(20_000, 8)).astype(np.float32)
    src = as_source(X)
    fetch = src.provider(1024, seed=0, dtype=__import__("ml_dtypes").bfloat16)
    chunk = fetch(0)
    assert chunk.dtype == np.dtype(__import__("ml_dtypes").bfloat16)
    cfg = BigMeansConfig(k=5, s=1024, n_chunks=6, impl="ref",
                         precision="bf16", prefetch=2)
    res = fit(src, cfg, method="streaming")
    assert np.isfinite(res.objective)
    assert res.n_chunks == 6


def test_memmap_provider_explicit_dtype_wins(tmp_path):
    from repro.api import MemmapSource

    X = np.random.default_rng(2).normal(size=(200, 4)).astype(np.float64)
    path = tmp_path / "data.npy"
    np.save(path, X)
    src = MemmapSource(path, dtype=np.float64)
    assert src.provider(16)(0).dtype == np.float64          # native default
    assert src.provider(16, dtype=np.float32)(0).dtype == np.float32


def test_config_precision_validation():
    from repro.api import BigMeansConfig

    with pytest.raises(ValueError, match="unknown precision"):
        BigMeansConfig(k=3, s=10, precision="fp16")
    with pytest.raises(ValueError, match="autotune"):
        BigMeansConfig(k=3, s=10, autotune=1)
    cfg = BigMeansConfig(k=3, s=10, precision="bf16", autotune=True)
    assert cfg.precision == "bf16"


# ---------------------------------------------------------------------------
# satellite: ops.fused_step fallback honors ref_chunked
# ---------------------------------------------------------------------------


def test_fused_step_fallback_honors_ref_chunked(monkeypatch):
    x, c = _blobs(m=200, n=16, k=4)
    seen = []
    real_assign = ops.assign

    def spy(xa, ca, **kw):
        seen.append(kw.get("impl"))
        return real_assign(xa, ca, **kw)

    monkeypatch.setattr(ops, "assign", spy)
    # weights force the two-pass fallback even inside the fused envelope
    w = jnp.ones((x.shape[0],))
    ops.fused_step(x, c, weights=w, impl="ref_chunked")
    assert seen == ["ref_chunked"]
    # envelope miss (c + sums blocks past the resident VMEM) also keeps the
    # bounded-working-set path
    seen.clear()
    kbig, nbig = 8192, 256
    from repro.kernels.fused_step import fits
    assert not fits(kbig, nbig)
    cbig = jax.random.normal(jax.random.PRNGKey(0), (kbig, nbig))
    xbig = jax.random.normal(jax.random.PRNGKey(1), (64, nbig))
    ops.fused_step(xbig, cbig, impl="ref_chunked")
    assert seen == ["ref_chunked"]


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------


@pytest.fixture
def clean_autotune():
    autotune.clear()
    was_enabled, was_path = autotune.enabled(), autotune.cache_path()
    yield
    autotune.clear()
    autotune.enable(was_enabled)
    autotune.set_cache_path(was_path)


def test_autotune_tilings_never_change_numerics(clean_autotune):
    """Acceptance: identical (sums, counts, obj) across candidate tilings.

    Integer-valued data makes every partial sum exactly representable in
    f32, so the comparison is bitwise — any tile-dependent accumulation
    difference would fail loudly.
    """
    key = jax.random.PRNGKey(0)
    x = jax.random.randint(key, (700, 24), -8, 8).astype(jnp.float32)
    c = jax.random.randint(jax.random.PRNGKey(1), (25, 24), -8, 8).astype(
        jnp.float32)
    outs = [fused_step_pallas(x, c, block_m=bm, interpret=True)
            for bm in (128, 256, 512)]
    for s, n, o in outs[1:]:
        np.testing.assert_array_equal(np.asarray(s), np.asarray(outs[0][0]))
        np.testing.assert_array_equal(np.asarray(n), np.asarray(outs[0][1]))
        assert float(o) == float(outs[0][2])

    xb, cb = x[None], c[None]
    outs = [fused_step_batched_pallas(xb, cb, block_m=bm, block_k=bk,
                                      block_n=bn, interpret=True)
            for bm, bk, bn in ((256, 128, 512), (128, 256, 256))]
    np.testing.assert_array_equal(np.asarray(outs[0][0]),
                                  np.asarray(outs[1][0]))
    np.testing.assert_array_equal(np.asarray(outs[0][1]),
                                  np.asarray(outs[1][1]))
    np.testing.assert_array_equal(np.asarray(outs[0][2]),
                                  np.asarray(outs[1][2]))


def test_autotune_candidates_include_shape_derived_default(clean_autotune):
    """Tuning must always time the tiling the un-tuned kernel would use,
    so a cached winner can never be slower than not tuning (n=20 resolves
    block_n=128, which the generic candidate grid does not contain)."""
    cands = autotune.candidates("fused_batched", b=4, m=16384, k=25, n=20,
                                precision="f32")
    assert cands[0] == {"block_m": 256, "block_k": 128, "block_n": 128}


def test_autotune_disabled_returns_defaults(clean_autotune):
    autotune.enable(False)
    blocks = autotune.get_blocks(
        "fused", lambda blk: (lambda: None),
        backend="interpret", b=1, m=256, k=25, n=20, precision="f32")
    assert blocks == {"block_m": 256, "block_k": None, "block_n": None,
                      "pipeline": "blocks"}


def test_autotune_cache_roundtrip(tmp_path, clean_autotune):
    """Cache write + reload: the winner is timed once, then served from disk."""
    cache = tmp_path / "tune.json"
    autotune.set_cache_path(cache)
    autotune.enable(True)

    calls = []

    def bench_factory(blocks):
        def run():
            calls.append(dict(blocks))
        return run

    kw = dict(backend="interpret", b=1, m=256, k=25, n=20, precision="bf16")
    first = autotune.get_blocks("fused", bench_factory, **kw)
    assert cache.exists()
    assert calls, "tuning should have timed candidates"

    # fresh process simulation: drop the in-memory cache, keep the file
    autotune.clear(disk=False)
    calls.clear()
    again = autotune.get_blocks("fused", bench_factory, **kw)
    assert again == first
    assert calls == [], "disk hit must not re-time"

    key = autotune.cache_key("fused", **kw)
    import json
    entries = json.loads(cache.read_text())["entries"]
    assert entries[key] == first


def test_fit_with_autotune_flag(clean_autotune):
    """cfg.autotune=True tunes for the call's duration, then restores the
    previous enable state (no sticky process-wide surprise sweeps)."""
    from repro.api import BigMeansConfig, fit

    autotune.enable(False)
    rng = np.random.default_rng(1)
    X = rng.normal(size=(5_000, 8)).astype(np.float32)
    cfg = BigMeansConfig(k=4, s=512, n_chunks=4, impl="ref", autotune=True)
    res = fit(X, cfg)
    assert not autotune.enabled()
    assert np.isfinite(res.objective)


def test_autotune_smoke_via_ops_interpret(clean_autotune):
    """ops consults the tuner and the tuned launch matches the oracle."""
    autotune.enable(True)
    x, c = _blobs(m=300, n=28, k=25)
    s_p, n_p, o_p = ops.fused_step(x, c, impl="pallas_interpret",
                                   precision="bf16")
    s_r, n_r, o_r = ops.fused_step(x, c, impl="ref", precision="bf16")
    np.testing.assert_array_equal(np.asarray(n_p), np.asarray(n_r))
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r),
                               rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(float(o_p), float(o_r), rtol=1e-2)


# ---------------------------------------------------------------------------
# int8 numerics (ISSUE 9)
# ---------------------------------------------------------------------------


def _int8_exact_blobs(m=300, n=24, k=25, seed=0):
    """Integer data on which int8 quantization is *exact*.

    One point row of +/-127 pins every per-feature scale to exactly 1
    (``s[f] = max|x[:, f]| / 127``); a 127 column in the centroids pins
    every per-row scale ``t[j]`` to 1.  Codes then reproduce the values
    bit-for-bit and every contraction/accumulation stays on integers below
    2^24, so ref-vs-Pallas comparisons are bitwise whatever the tiling.
    """
    rng = np.random.default_rng(seed)
    x = rng.integers(-8, 9, size=(m, n)).astype(np.float32)
    x[0, :] = 127.0
    x[1, :] = -127.0
    c = rng.integers(-8, 9, size=(k, n)).astype(np.float32)
    c[:, 0] = 127.0
    return jnp.asarray(x), jnp.asarray(c)


def test_int8_precision_policy():
    assert px.check("int8") == "int8"
    assert px.storage_dtype("int8") == jnp.int8
    assert px.resolve("auto", jnp.int8) == "int8"
    qx = px.cast_storage(jnp.ones((4, 3)), "int8")
    assert isinstance(qx, px.QuantizedChunk)
    assert px.cast_storage(qx, "int8") is qx               # idempotent


def test_int8_quantization_exact_on_pinned_data():
    x, _ = _int8_exact_blobs()
    qx = px.quantize_chunk(x)
    np.testing.assert_array_equal(np.asarray(qx.scale),
                                  np.ones(x.shape[1], np.float32))
    np.testing.assert_array_equal(np.asarray(px.dequantize(qx)),
                                  np.asarray(x))
    # host-thread quantization is the bitwise twin of the device path
    qh, sh = px.host_quantize(np.asarray(x))
    np.testing.assert_array_equal(np.asarray(qx.q), qh)
    np.testing.assert_array_equal(np.asarray(qx.scale), sh)


@pytest.mark.parametrize("pipeline", ["blocks", "dma"])
def test_int8_fused_pallas_bitwise_matches_ref(pipeline):
    """Acceptance: ref-vs-Pallas parity on integer data is *bitwise* —
    int8 contractions are exact int32 and every f32 value is an integer
    below 2^24, so any tiling- or pipeline-dependent difference in the
    quantized math fails loudly, on both pipelines."""
    x, c = _int8_exact_blobs()
    s_r, n_r, o_r = ops.fused_step(x, c, impl="ref", precision="int8")
    for bm in (128, 256):
        s_p, n_p, o_p = fused_step_pallas(
            x, c, precision="int8", block_m=bm, pipeline=pipeline,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))
        np.testing.assert_array_equal(np.asarray(n_p), np.asarray(n_r))
        assert float(o_p) == float(o_r), (bm, pipeline)
    # a pre-quantized chunk (what the streaming prefetcher ships) is the
    # same computation as quantize-at-entry
    s_q, n_q, o_q = fused_step_pallas(
        px.quantize_chunk(x), c, pipeline=pipeline, interpret=True)
    np.testing.assert_array_equal(np.asarray(s_q), np.asarray(s_r))
    assert float(o_q) == float(o_r)


@pytest.mark.parametrize("m,n,k", [(257, 29, 5), (100, 30, 129)])
def test_int8_assign_parity_and_padding_invariance(m, n, k):
    """Padded lanes never win an argmin; zero-padded features change
    nothing (their quantization scale floors, codes stay 0)."""
    x, c = _int8_exact_blobs(m, n, k, seed=3)
    ids_p, d_p = ops.assign(x, c, impl="pallas_interpret", precision="int8")
    ids_r, d_r = ref.assign_ref(x, c, precision="int8")
    assert int(jnp.max(ids_p)) < k and int(jnp.min(ids_p)) >= 0
    np.testing.assert_array_equal(np.asarray(ids_p), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(d_p), np.asarray(d_r))
    # same data embedded in a wider zero-padded feature space: identical
    xw = jnp.pad(x, ((0, 0), (0, 7)))
    cw = jnp.pad(c, ((0, 0), (0, 7)))
    ids_w, d_w = ops.assign(xw, cw, impl="pallas_interpret",
                            precision="int8")
    np.testing.assert_array_equal(np.asarray(ids_w), np.asarray(ids_r))
    np.testing.assert_array_equal(np.asarray(d_w), np.asarray(d_r))


@pytest.mark.parametrize("dataset", ["hepmass-16k", "road3d-24k"])
def test_fit_int8_within_1pct_of_f32_on_quick_datasets(dataset):
    """Acceptance: <1% relative f_best drift vs the f32 run, same seeds,
    on the evalsuite quick-tier datasets (real registry memmaps, reduced
    chunk budget to keep tier-1 wall time down)."""
    from repro.api import BigMeansConfig, fit
    from repro.evalsuite import datasets as ds

    spec = ds.get_dataset(dataset)
    src = ds.source(spec)
    cfg = BigMeansConfig(k=spec.k, s=spec.s, n_chunks=8, impl="ref", seed=0)
    r32 = fit(src, cfg)
    r8 = fit(src, cfg, precision="int8")
    rel = abs(r8.objective - r32.objective) / r32.objective
    assert rel < 0.01, (dataset, r32.objective, r8.objective, rel)


@pytest.fixture
def clean_demotions():
    ops.reset_kernel_demotions()
    yield
    ops.reset_kernel_demotions()


def test_warm_assign_int8_demotes_under_kernel_failure(clean_demotions):
    """A Pallas failure on the int8 serving shape demotes exactly that
    (shape, precision) key during warmup and serving falls back to ref."""
    from repro.engine import faults

    with faults.kernel_failure("assign"):
        got = ops.warm_assign(32, 256, 16, impl="pallas_interpret",
                              precision="int8")
    assert got == "ref"
    demos = [d for d in ops.kernel_demotions()
             if d["op"] == "assign" and d["shape"] == (1, 32, 256, 16)
             and d["precision"] == "int8"]
    assert demos, ops.kernel_demotions()
    # the demoted shape serves bitwise-correct results through the ref path
    x, c = _int8_exact_blobs(32, 16, 256, seed=5)
    ids, d = ops.assign(x, c, impl="pallas_interpret", precision="int8")
    ids_r, d_r = ref.assign_ref(x, c, precision="int8")
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_r))


# ---------------------------------------------------------------------------
# k > 128 argmin tiling + DMA pipeline (ISSUE 9)
# ---------------------------------------------------------------------------


def test_k256_runs_single_fused_kernel_matches_oracle():
    """Acceptance: a k=256 shape that the legacy envelope (k <= 128) sent
    to the two-pass fallback now runs the single fused kernel, bitwise
    equal to the oracle on integer data, on both pipelines."""
    from repro.kernels.fused_step import LEGACY_MAX_K, fits

    k, n = 256, 20
    assert k > LEGACY_MAX_K and fits(k, n)
    x, c = _int8_exact_blobs(m=200, n=n, k=k, seed=7)
    s_r, n_r, o_r = ops.fused_step(x, c, impl="ref", precision="f32")
    for pipeline in ("blocks", "dma"):
        s_p, n_p, o_p = fused_step_pallas(x, c, precision="f32",
                                          pipeline=pipeline, interpret=True)
        np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))
        np.testing.assert_array_equal(np.asarray(n_p), np.asarray(n_r))
        assert float(o_p) == float(o_r), pipeline


def test_autotune_candidates_cover_k256_and_dma(clean_autotune):
    """The tuner's fused candidate set covers the widened envelope: the
    k=256 cell gets real candidates, both pipelines are timed, and the
    shape-derived 'blocks' default stays first (ties keep history)."""
    cands = autotune.candidates("fused", b=1, m=4096, k=256, n=20,
                                precision="f32")
    assert cands[0]["pipeline"] == "blocks"
    assert any(c["pipeline"] == "dma" for c in cands)
    from repro.kernels import fused_step as fused
    for c in cands:
        assert fused.resident_bytes(256, 20, c["block_k"], c["block_n"]) \
            <= fused.RESIDENT_BYTES, c


def test_unknown_pipeline_rejected():
    x, c = _int8_exact_blobs(m=64, n=8, k=4)
    with pytest.raises(ValueError, match="unknown pipeline"):
        fused_step_pallas(x, c, pipeline="prefetch", interpret=True)


# ---------------------------------------------------------------------------
# committed autotune profile + cache observability (ISSUE 9)
# ---------------------------------------------------------------------------

_PROFILE = __import__("pathlib").Path(__file__).resolve().parent.parent \
    / "results" / "autotune" / "interpret.json"


def test_committed_profile_loads_and_is_consulted(clean_autotune):
    """Every entry in the committed per-backend profile round-trips: the
    lazy disk load accepts the file, get_blocks serves each key without
    re-timing (the bench spy must never run), and no load anomaly event
    is recorded."""
    import json

    data = json.loads(_PROFILE.read_text())
    assert data["version"] == 1 and data["entries"]
    autotune.set_cache_path(_PROFILE)
    autotune.enable(True)
    n_events = len(autotune.events())

    timed = []

    def bench_factory(blocks):
        return lambda: timed.append(dict(blocks))

    for key, entry in data["entries"].items():
        kind, backend, b, m, k, n, prec = key.split("|")
        got = autotune.get_blocks(
            kind, bench_factory, backend=backend, b=int(b[1:]),
            m=int(m[1:]), k=int(k[1:]), n=int(n[1:]), precision=prec)
        assert got == entry, key
    assert timed == [], "profile hits must not re-time candidates"
    assert autotune.events()[n_events:] == []


def test_corrupt_cache_ignored_with_event(tmp_path, clean_autotune):
    cache = tmp_path / "tune.json"
    cache.write_text("{this is not json")
    autotune.set_cache_path(cache)
    autotune.enable(True)
    n_events = len(autotune.events())
    blocks = autotune.get_blocks(
        "fused", None, backend="interpret", b=1, m=64, k=5, n=8,
        precision="f32")
    assert blocks == {"block_m": 256, "block_k": None, "block_n": None,
                      "pipeline": "blocks"}
    new = autotune.events()[n_events:]
    assert len(new) == 1
    kind, path, reason = new[0]
    assert kind == "autotune_cache_ignored"
    assert path == str(cache)
    assert reason.startswith("unreadable")


def test_stale_schema_cache_ignored_with_event(tmp_path, clean_autotune):
    import json

    cache = tmp_path / "tune.json"
    cache.write_text(json.dumps({"version": 99, "entries": {}}))
    autotune.set_cache_path(cache)
    n_events = len(autotune.events())
    autotune.get_blocks("fused", None, backend="interpret", b=1, m=64,
                        k=5, n=8, precision="f32")
    new = autotune.events()[n_events:]
    assert new == [("autotune_cache_ignored", str(cache),
                    "stale schema version 99")]


def test_malformed_cache_entry_ignored_with_event(tmp_path, clean_autotune):
    """One bad entry is skipped (with an event); good entries still load."""
    import json

    good_key = autotune.cache_key("fused", backend="interpret", b=1, m=64,
                                  k=5, n=8, precision="f32")
    bad_key = autotune.cache_key("fused", backend="interpret", b=1, m=64,
                                 k=5, n=8, precision="bf16")
    good = {"block_m": 128, "block_k": 128, "block_n": 256,
            "pipeline": "dma"}
    cache = tmp_path / "tune.json"
    cache.write_text(json.dumps({
        "version": 1,
        "entries": {good_key: good, bad_key: {"block_m": [128]}}}))
    autotune.set_cache_path(cache)
    n_events = len(autotune.events())
    got = autotune.get_blocks("fused", None, backend="interpret", b=1,
                              m=64, k=5, n=8, precision="f32")
    assert got == good
    assert autotune.events()[n_events:] == [
        ("autotune_cache_entry_ignored", str(cache), bad_key)]


def test_fit_surfaces_cache_ignored_event_in_trace(tmp_path, clean_autotune):
    """End-to-end observability: a corrupt on-disk cache consulted during
    fit()'s pre-tune lands in the run trace instead of crashing (or being
    silently swallowed)."""
    from repro.api import BigMeansConfig, fit

    cache = tmp_path / "tune.json"
    cache.write_text("%% corrupt %%")
    autotune.set_cache_path(cache)
    rng = np.random.default_rng(3)
    # an unusual shape: block sizes are read at trace time, so a shape any
    # other test already jitted would skip get_blocks (and the lazy load)
    X = rng.normal(size=(4_200, 9)).astype(np.float32)
    cfg = BigMeansConfig(k=7, s=600, n_chunks=2, impl="pallas_interpret",
                         seed=0)
    res = fit(X, cfg)
    assert np.isfinite(res.objective)
    evs = [t for t in res.trace
           if isinstance(t, tuple) and isinstance(t[0], str)
           and t[0] == "autotune_cache_ignored"]
    assert evs and evs[0][1] == str(cache), res.trace[-5:]


# ---------------------------------------------------------------------------
# f32 means f32 on every backend; no silent leaving of the Pallas path
# ---------------------------------------------------------------------------


def test_f32_contractions_request_highest_precision():
    """XLA:TPU's DEFAULT precision for f32 operands is one bf16 pass; the
    f32 policy must ask for HIGHEST (the CPU computes f32 either way, so
    only the request itself can be checked here)."""
    a = jnp.ones((8, 4))
    dn = (((1,), (1,)), ((), ()))

    def precisions(policy):
        jaxpr = jax.make_jaxpr(lambda x: px.dot(x, x, dn, policy))(a)
        return [e.params["precision"] for e in jaxpr.eqns
                if e.primitive.name == "dot_general"]

    assert precisions("f32") == [(jax.lax.Precision.HIGHEST,) * 2]
    assert precisions("bf16") == [None]


@pytest.mark.parametrize("precision", ["f32", "bf16", "bf16x3"])
def test_ref_point_norms_of_stored_points(precision):
    """The oracle's ||x||^2 is that of the points as stored under the
    policy — what the kernels stream — whatever dtype the caller passed."""
    x, c = _blobs(m=300, n=28, k=25)
    stored = x.astype(px.storage_dtype(precision))
    np.testing.assert_array_equal(
        np.asarray(ref.pairwise_sqdist_ref(x, c, precision=precision)),
        np.asarray(ref.pairwise_sqdist_ref(stored, c, precision=precision)))


@pytest.mark.parametrize("batched", [False, True])
def test_pallas_envelope_miss_is_recorded(clean_demotions, batched):
    """A Pallas request routed to the two-pass path by the envelope shows
    in kernel_demotions(), naming op and shape; a ref request does not."""
    from repro.kernels.fused_step import max_k

    k, n, m = max_k(8) + 8, 8, 64
    x = jax.random.normal(jax.random.PRNGKey(0), (m, n))
    c = jax.random.normal(jax.random.PRNGKey(1), (k, n))
    if batched:
        x, c = x[None], c[None]
    step = ops.fused_step_batched if batched else ops.fused_step
    step(x, c, impl="ref")
    assert ops.kernel_demotions() == []
    with pytest.warns(RuntimeWarning, match="envelope"):
        step(x, c, impl="pallas_interpret")
    (demo,) = ops.kernel_demotions()
    assert demo["op"] == ("fused_batched" if batched else "fused")
    assert demo["shape"] == (1, m, k, n)
    assert "envelope" in demo["error"]
