"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The chip's compiler is installed even where no chip is attached: these
tests describe a ``v5e:2x2`` topology, lower each kernel at a real width
against one of its devices and compile it, which raises what Mosaic would
raise on the chip (misaligned slices, VMEM overruns) — failures that
interpret mode cannot see.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so describing it while
pytest workers import this file would break every other worker.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.api import BigMeansConfig
from repro.core.bigmeans import BigMeansState
from repro.engine import incore, stream
from repro.engine.topology import StreamMesh
from repro.kernels import precision as px
from repro.kernels.distance import assign_pallas
from repro.kernels.fused_step import (
    fused_step_batched_pallas,
    fused_step_pallas,
)
from repro.kernels.update import update_pallas

S, N, K = 64_000, 28, 25          # HEPMASS surrogate chunk, paper k


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as exc:
            pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _fused_args(sharding, m, n, k, precision, batch=None):
    lead = () if batch is None else (batch,)
    cent = jax.ShapeDtypeStruct(lead + (k, n), jnp.float32, sharding=sharding)
    if precision == "int8":
        q = jax.ShapeDtypeStruct(lead + (m, n), jnp.int8, sharding=sharding)
        scale = jax.ShapeDtypeStruct(lead + (n,), jnp.float32,
                                     sharding=sharding)
        return (q, scale, cent)
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    return (jax.ShapeDtypeStruct(lead + (m, n), dtype, sharding=sharding),
            cent)


def _fused(precision, **kw):
    if precision == "int8":
        return lambda q, scale, c: fused_step_pallas(
            px.QuantizedChunk(q, scale), c, **kw)
    return lambda x, c: fused_step_pallas(x, c, precision=precision, **kw)


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("pipeline", ["blocks", "dma"])
def test_fused_step_paper_shape_compiles(one_chip, pipeline, precision):
    text = _compile(_fused(precision, pipeline=pipeline),
                    *_fused_args(one_chip, S, N, K, precision))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_fused_step_batched_b8_compiles(one_chip, precision):
    if precision == "int8":
        fn = lambda q, scale, c: fused_step_batched_pallas(  # noqa: E731
            px.QuantizedChunk(q, scale), c)
    else:
        fn = lambda x, c: fused_step_batched_pallas(  # noqa: E731
            x, c, precision=precision)
    text = _compile(fn, *_fused_args(one_chip, S, N, K, precision, batch=8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("pipeline", ["blocks", "dma"])
def test_fused_step_k256_compiles(one_chip, pipeline, precision):
    """k_pad > 128: the running argmin reads centroid tiles past lane 128."""
    text = _compile(_fused(precision, pipeline=pipeline),
                    *_fused_args(one_chip, S, 20, 256, precision))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pipeline,k,n,block_m", [
    ("dma", 256, 4096, 256),
    ("blocks", 1024, 1024, 512),
    ("blocks", 8192, 128, 256),
])
def test_fused_step_envelope_corners_compile(one_chip, pipeline, k, n,
                                             block_m):
    """The widest shapes ``fits`` admits stay inside the kernel's VMEM."""
    text = _compile(_fused("f32", pipeline=pipeline, block_m=block_m),
                    *_fused_args(one_chip, 8192, n, k, "f32"))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
def test_fused_step_batched_k4096_compiles(one_chip, precision):
    """SIFT1M's codebook shape: 8 lane tiles of 512 centroids, the 2 MiB
    centroid and sums blocks resident."""
    if precision == "int8":
        fn = lambda q, scale, c: fused_step_batched_pallas(  # noqa: E731
            px.QuantizedChunk(q, scale), c)
    else:
        fn = lambda x, c: fused_step_batched_pallas(  # noqa: E731
            x, c, precision=precision)
    text = _compile(fn, *_fused_args(one_chip, S, 128, 4096, precision,
                                     batch=8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("bucket", [64, 4096])
def test_assign_serving_bucket_compiles(one_chip, bucket):
    x = jax.ShapeDtypeStruct((bucket, N), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((K, N), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compile(assign_pallas, x, c)


def test_update_compiles(one_chip):
    x = jax.ShapeDtypeStruct((S, N), jnp.float32, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip)
    text = _compile(lambda a, i: update_pallas(a, i, K), x, ids)
    assert "tpu_custom_call" in text


M_HEPMASS = 10_500_000
GIB = 2 ** 30


def _fit_kwargs(precision):
    return dict(k=K, s=S, batch=8, rounds=2, sync_every=2, max_iters=300,
                tol=1e-4, candidates=3, impl="pallas", with_replacement=True,
                precision=precision, gather="packed")


def _chunk_gathers(text, streams=8):
    """``slice_sizes`` of each gather of a whole chunk batch's rows."""
    return {line.split("slice_sizes={")[1].split("}")[0]
            for line in text.splitlines()
            if " gather(" in line and f"[{streams},{S}," in line}


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_packed_gather_fit_compiles_at_hepmass_size(one_chip, precision):
    """The in-core fit at HEPMASS's 10.5M x 28 gathers each chunk row as one
    128-lane row of the packed copy, not as a point of the feature-major
    dataset, and the copy stays within a few GiB of the 16 GiB chip."""
    X = jax.ShapeDtypeStruct((M_HEPMASS, N), jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = incore.batched_local.lower(
        X, key, **_fit_kwargs(precision)).compile()
    assert _chunk_gathers(compiled.as_text()) == {"1,128"}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 6 * GIB


def test_codebook_fit_compiles_at_sift1m_size(one_chip):
    """The in-core fit of a k = 4096 codebook on 1M x 128 runs the fused
    kernel, and its program's temporaries stay far below the chip: XLA
    fuses K-means++'s [B, s, k] start into its minimum."""
    X = jax.ShapeDtypeStruct((1_000_000, 128), jnp.float32,
                             sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    kw = dict(_fit_kwargs("f32"), k=4096, gather="rows")
    compiled = incore.batched_local.lower(X, key, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * GIB


def _mesh(topo, axis):
    return Mesh(np.array(topo.devices[:4]), (axis,))


def test_stream_mesh_step_compiles_on_four_chips(topo):
    """The streaming step over a 4-chip stream mesh: XLA cannot partition a
    Mosaic kernel itself, so the step must run under ``shard_map``."""
    mesh = _mesh(topo, "streams")
    sh = NamedSharding(mesh, P("streams"))
    b = 8

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    states = BigMeansState(arg((b, K, N), jnp.float32), arg((b, K), bool),
                           arg((b,), jnp.float32), arg((b,), jnp.int32),
                           arg((b,), jnp.float32))
    cfg = BigMeansConfig(k=K, s=S, batch=b, impl="pallas", precision="f32")
    step = stream._StepKernel(cfg, None, StreamMesh(mesh))._sharded_step()
    text = step.lower(arg((b, S, N), jnp.float32), states,
                      arg((b, 2), jnp.uint32)).compile().as_text()
    assert "tpu_custom_call" in text


def test_worker_mesh_sharded_compiles_on_four_chips(topo):
    mesh = _mesh(topo, "data")
    X = jax.ShapeDtypeStruct((4 * S, N), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    text = _compile(
        lambda x, k: incore.worker_sharded(
            x, k, mesh=mesh, k=K, s=S, chunks_per_worker=2, sync_every=2,
            impl="pallas", precision="f32"), X, key)
    assert "tpu_custom_call" in text


def test_packed_gather_stream_mesh_fit_compiles_on_four_chips(topo):
    """Each chip of the stream mesh packs its replica of the dataset and
    gathers its streams' chunk rows from that copy."""
    mesh = _mesh(topo, "streams")
    rep = NamedSharding(mesh, P())
    X = jax.ShapeDtypeStruct((M_HEPMASS, N), jnp.float32, sharding=rep)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    compiled = incore.batched_stream_mesh.lower(
        X, key, mesh=mesh, stream_axis="streams",
        **_fit_kwargs("f32")).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _chunk_gathers(text, streams=2) == {"1,128"}
