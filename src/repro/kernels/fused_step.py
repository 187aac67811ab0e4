"""Fused Lloyd-iteration kernel: assignment + update + objective in ONE pass.

The two-kernel formulation streams the chunk from HBM twice per iteration
(assign reads X, update reads X again).  This kernel computes, per point
tile resident in VMEM:

    scores  = ||c||^2 - 2 x @ c^T          (MXU)
    idx     = argmin(scores)               (VPU)
    sums   += onehot(idx)^T @ x            (MXU, same resident tile)
    counts += colsum(onehot)
    obj    += sum(min_dist)

halving the dominant HBM traffic of Big-means' inner loop.

k and n are tiled *inside* the kernel: k is processed in ``block_k`` lane
tiles with a running (min, argmin) pair carried across tiles — the full
s x k distance block is never materialized — and the distance matmul
contracts n in ``block_n`` tiles.  Both tile loops are unrolled at trace
time; the default lane tile grows with k (:func:`_default_block_k`) so a
kernel never walks more than :data:`UNROLL_K_TILES` of them: 128 lanes up
to k = 1024, 512 at k = 4096.  The envelope :func:`fits` is what stays
resident in VMEM for the single and batched variants alike: the centroid
block and the sums block, ``k_pad x n_pad`` four-byte words each,
double-buffered, at most :data:`RESIDENT_BYTES` (16 MiB, so k_pad * n_pad
<= 1M: k = 4096 at n = 256, k = 8192 at n = 128), with n <= 4096.

The update contracts the one-hot assignment with the point tile.  Past
k = 1024, where the lane tile widens, the kernel is bound by the MXU and
the update is half its contractions: a 0/1 matrix is exact in bf16, so
under ``'f32'`` it is three single-pass bf16 products with the point
tile's three bf16 parts (:func:`~repro.kernels.precision.onehot_dot`)
rather than the six passes of ``Precision.HIGHEST``.  Every k <= 1024
keeps the policy's dot and the program it compiled to before.

Mixed precision (``precision='bf16'``): the chunk and centroids are stored
and streamed bf16 — halving the remaining HBM bytes again — and both MXU
contractions take bf16 operands.  Everything that decides or accumulates is
f32: the score accumulator (``preferred_element_type``), ``||c||^2``
(from the full-width centroids), ``||x||^2`` (of the stored points), sums,
counts and the objective.  ``'bf16x3'`` keeps f32 storage and runs
each contraction as three compensated bf16 products (near-f32 numerics at
bf16 MXU rates; no bandwidth change).

``'int8'`` streams the chunk as int8 codes + per-feature scales (a quarter
of the f32 bytes; see :mod:`repro.kernels.precision`): centroids are
re-quantized per iteration into the chunk's scaled feature space with
per-row scales ``t`` so the distance contraction is int8 x int8 -> int32
(exact) with ``t`` factoring out per score column; the one-hot update
contraction is 0/1 x int8 -> int32 (exact), scaled to data space after the
kernel; and the correction terms — full-width ``||c||^2``, dequantized
``||x||^2`` — plus the running argmin, counts and objective stay f32.

Pipelines (single-chunk kernel):

* ``pipeline='blocks'`` — the classic Pallas grid: one program per point
  tile, the BlockSpec machinery streams x tiles HBM->VMEM.
* ``pipeline='dma'``    — double-buffered chunk DMA: x stays in HBM/ANY and
  one program walks the point tiles with explicit ``make_async_copy`` into
  a two-slot VMEM scratch, starting the copy of tile i+1 before computing
  on tile i, so HBM streaming overlaps MXU compute.  Same math, same
  results; registered as an autotune candidate so the tuner picks whichever
  wins on the backend.

``ops.fused_step`` / ``ops.fused_step_batched`` fall back to the two-pass
path outside the envelope or when point weights are used.  Block sizes
default to the module constants; ``ops`` overrides them with autotuned
tilings (``repro.kernels.autotune``) — tile/pipeline choice is perf-only
and never changes results.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import precision as px

_BIG = 1e30

# VMEM-working-set envelope: k and n are tiled inside the kernel, so the
# wall is the resident c + sums blocks, not the lane width.
MAX_N = 4096
RESIDENT_BYTES = 16 * 2**20    # c + sums blocks, double-buffered, 4 B words

# The most lane tiles of k a kernel walks at its default tiling: past
# 8 x 128 centroids the default lane tile widens instead.
UNROLL_K_TILES = 8

# Historical single-chunk envelope (pre-tiling), kept for tests/docs: shapes
# beyond it used to fall back to the two-pass ref path.
LEGACY_MAX_K = 128
LEGACY_MAX_N = 1024

_BLOCK_K = 128                 # narrowest lane tile for the running argmin
_BLOCK_N = 512                 # contraction tile for the distance matmul

PIPELINES = ("blocks", "dma")

# Scoped VMEM for the kernels (v5e has 128 MiB per core).  The compiler's
# default limit refuses the envelope's wide corners — n = 4096 with
# k_pad = 256 in f32, or block_m = 512 at n = 2048 — which fit in 64 MiB
# (tests/test_tpu_compile.py).
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2**20)


def _pad_to(a, size, axis, value=0.0):
    pad = size - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _default_block_k(k: int) -> int:
    """The narrowest multiple of 128 lanes that covers k in at most
    UNROLL_K_TILES tiles (128 for every k <= 1024)."""
    groups = -(-k // _BLOCK_K)
    return _BLOCK_K * -(-groups // UNROLL_K_TILES)


def _batched_tiles(k: int, n: int, block_k: int | None = None,
                   block_n: int | None = None) -> tuple[int, int, int, int]:
    """(k_pad, n_pad, block_k, block_n) used by the fused kernels."""
    block_k = _default_block_k(k) if block_k is None else block_k
    k_pad = -(-k // block_k) * block_k
    n_pad = -(-n // 128) * 128
    if block_n is None:
        block_n = n_pad if n_pad <= _BLOCK_N else _BLOCK_N
    n_pad = -(-n_pad // block_n) * block_n
    return k_pad, n_pad, block_k, block_n


def _lane_tiles(row, k_pad: int, block_k: int, value=0.0):
    """Pad per-centroid rows ``[..., k]`` to ``k_pad`` and lay them out
    tile-major as ``[..., k_pad // block_k, 1, block_k]``."""
    row = _pad_to(row, k_pad, row.ndim - 1, value=value)
    return row.reshape(row.shape[:-1] + (k_pad // block_k, 1, block_k))


def resident_bytes(k: int, n: int, block_k: int | None = None,
                   block_n: int | None = None) -> int:
    """VMEM bytes of the blocks a fused kernel keeps resident at this tiling:
    the ``k_pad x n_pad`` centroid and sums blocks (f32, or int8 codes and
    int32 sums: at most four bytes a word), each double-buffered."""
    k_pad, n_pad, _, _ = _batched_tiles(k, n, block_k, block_n)
    return 2 * 2 * 4 * k_pad * n_pad


def fits(k: int, n: int) -> bool:
    """Whether ``(k, n)`` runs in the fused kernels at the default tiling:
    ``n <= MAX_N`` and :func:`resident_bytes` within RESIDENT_BYTES."""
    return n <= MAX_N and resident_bytes(k, n) <= RESIDENT_BYTES


def fits_tiling(k: int, n: int, block_k: int, block_n: int) -> bool:
    """Whether a tiling is one the kernels take at ``(k, n)``: the resident
    blocks within RESIDENT_BYTES, at most UNROLL_K_TILES lane tiles of k."""
    return resident_bytes(k, n, block_k, block_n) <= RESIDENT_BYTES \
        and k_tiles(k, block_k) <= UNROLL_K_TILES


def max_k(n: int) -> int:
    """The largest k that :func:`fits` at width ``n`` (0 past MAX_N)."""
    if n > MAX_N:
        return 0
    return RESIDENT_BYTES // resident_bytes(_BLOCK_K, n) * _BLOCK_K


def k_tiles(k: int, block_k: int | None = None) -> int:
    """Lane tiles the kernels walk for ``k`` centroids."""
    block_k = _default_block_k(k) if block_k is None else block_k
    return -(-k // block_k)


# Single and batched kernels share one envelope since the k/n tiling moved
# into both bodies.
fits_batched = fits


def _unpack_fused_refs(args, precision: str):
    """(x, c, csq, t, scale, sums, counts, obj, rest) from positional refs."""
    if precision == "int8":
        x_ref, c_ref, csq_ref, t_ref, scale_ref = args[:5]
        rest = args[5:]
    else:
        x_ref, c_ref, csq_ref = args[:3]
        t_ref = scale_ref = None
        rest = args[3:]
    sums_ref, counts_ref, obj_ref = rest[:3]
    return x_ref, c_ref, csq_ref, t_ref, scale_ref, sums_ref, counts_ref, \
        obj_ref, rest[3:]


def _accumulate(i, x, c, csq, t, scale, sums_ref, counts_ref, obj_ref, *,
                lead: tuple, m: int, block_m: int, block_k: int,
                block_n: int, precision: str):
    """Process one resident point tile and accumulate into the output refs.

    Scores ``||c||^2 - 2 x.c`` per k lane tile with a running (min,
    argmin) pair carried across tiles, then the one-hot update of each
    tile's sums and counts, then the tile's objective; both tile loops are
    unrolled at trace time.  ``x`` [bm, n_pad] is the point tile and ``i``
    its index (python int or tracer); ``c`` [k_pad, n_pad] the centroids.
    ``csq`` and, under int8, ``t`` (the per-row centroid scales) come
    tile-major ``[nk, 1, block_k]`` (see :func:`_lane_tiles`) so tile
    ``j`` is a leading-axis index, never a lane slice at an offset: Mosaic
    cannot broadcast a ``(1, block_k)`` slice taken at lane offset
    ``j * block_k > 0``.  Under int8 ``scale`` [1, n_pad] holds the
    per-feature chunk scales.  ``lead`` indexes the output refs' batch
    axis (``(0,)``) or nothing.
    """
    bm, n_pad = x.shape
    nk, nn = csq.shape[0], n_pad // block_n
    int8 = precision == "int8"

    best = jnp.full((bm,), _BIG, jnp.float32)
    bidx = jnp.zeros((bm,), jnp.int32)
    for j in range(nk):
        ct = c[j * block_k:(j + 1) * block_k]                # [bk, n_pad]
        if int8:
            idots = jnp.zeros((bm, block_k), jnp.int32)
            for u in range(nn):
                sl = slice(u * block_n, (u + 1) * block_n)
                idots += px.intdot(x[:, sl], ct[:, sl],
                                   (((1,), (1,)), ((), ())))
            dots = idots.astype(jnp.float32) * t[j]
        else:
            dots = jnp.zeros((bm, block_k), jnp.float32)
            for u in range(nn):
                sl = slice(u * block_n, (u + 1) * block_n)
                dots += px.dot(x[:, sl], ct[:, sl], (((1,), (1,)), ((), ())),
                               precision)
        sc = csq[j] - 2.0 * dots
        tmin = jnp.min(sc, axis=1)
        targ = jnp.argmin(sc, axis=1).astype(jnp.int32) + j * block_k
        take = tmin < best
        best = jnp.where(take, tmin, best)
        bidx = jnp.where(take, targ, bidx)

    if int8:
        deq = x.astype(jnp.float32) * scale                  # [bm, n_pad]
        xsq = jnp.sum(deq * deq, axis=1)
    else:
        xsq = px.sqnorm(x, axis=1)
    mind = jnp.maximum(best + xsq, 0.0)
    rows = i * block_m + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    validb = rows < m                                        # [bm, 1] bool
    valid = validb.astype(jnp.float32)

    for j in range(nk):
        lanes = (jax.lax.broadcasted_iota(jnp.int32, (bm, block_k), 1)
                 + j * block_k)
        hit = (bidx[:, None] == lanes) & validb              # [bm, bk]
        ksl = slice(j * block_k, (j + 1) * block_k)
        if int8:
            part = px.intdot(hit.astype(jnp.int8), x,
                             (((0,), (0,)), ((), ())))       # [bk, n_pad] i32
        elif c.shape[0] > _BLOCK_K * UNROLL_K_TILES:
            part = px.onehot_dot(hit, x, (((0,), (0,)), ((), ())), precision)
        else:
            part = px.dot(hit.astype(jnp.float32), x,
                          (((0,), (0,)), ((), ())), precision)
        sums_ref[lead + (ksl, slice(None))] += part
        counts_ref[lead + (slice(None), ksl)] += jnp.sum(
            hit.astype(jnp.float32), axis=0, keepdims=True)
    contrib = jnp.sum(mind[:, None] * valid, keepdims=True)[0:1, 0:1]
    obj_ref[...] += contrib.reshape(obj_ref.shape)


def _fused_kernel(*args, m: int, block_m: int, block_k: int, block_n: int,
                  precision: str):
    (x_ref, c_ref, csq_ref, t_ref, scale_ref, sums_ref, counts_ref, obj_ref,
     _) = _unpack_fused_refs(args, precision)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        obj_ref[...] = jnp.zeros_like(obj_ref)

    _accumulate(
        i, x_ref[...], c_ref[...], csq_ref[...],
        None if t_ref is None else t_ref[...],
        None if scale_ref is None else scale_ref[...],
        sums_ref, counts_ref, obj_ref, lead=(), m=m, block_m=block_m,
        block_k=block_k, block_n=block_n, precision=precision)


def _fused_dma_kernel(*args, m: int, block_m: int, block_k: int,
                      block_n: int, precision: str, num_tiles: int):
    """Double-buffered variant: x lives in HBM/ANY; explicit async copies
    stream point tiles into a two-slot VMEM scratch so the DMA of tile i+1
    overlaps compute on tile i."""
    (x_hbm, c_ref, csq_ref, t_ref, scale_ref, sums_ref, counts_ref, obj_ref,
     rest) = _unpack_fused_refs(args, precision)
    scratch, sem = rest

    sums_ref[...] = jnp.zeros_like(sums_ref)
    counts_ref[...] = jnp.zeros_like(counts_ref)
    obj_ref[...] = jnp.zeros_like(obj_ref)

    c = c_ref[...]
    csq = csq_ref[...]
    t = None if t_ref is None else t_ref[...]
    scale = None if scale_ref is None else scale_ref[...]

    def dma(slot, i):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(i * block_m, block_m)], scratch.at[slot],
            sem.at[slot])

    dma(0, 0).start()

    def body(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < num_tiles)
        def _prefetch_next():
            dma(jax.lax.rem(i + 1, 2), i + 1).start()

        dma(slot, i).wait()
        _accumulate(
            i, scratch[slot], c, csq, t, scale, sums_ref, counts_ref,
            obj_ref, lead=(), m=m, block_m=block_m, block_k=block_k,
            block_n=block_n, precision=precision)
        return carry

    jax.lax.fori_loop(0, num_tiles, body, 0)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "pipeline", "precision",
                     "interpret"),
)
def fused_step_pallas(
    x,
    c: jax.Array,
    *,
    block_m: int = 256,
    block_k: int | None = None,
    block_n: int | None = None,
    pipeline: str = "blocks",
    precision: str = "f32",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x [m,n], c [k,n] -> (sums f32 [k,n], counts f32 [k], obj f32 scalar).

    ``x`` may be a plain array or (under ``'int8'``) a pre-quantized
    :class:`~repro.kernels.precision.QuantizedChunk`.
    """
    px.check(precision)
    if pipeline not in PIPELINES:
        raise ValueError(f"unknown pipeline {pipeline!r}; known: {PIPELINES}")
    int8 = precision == "int8" or isinstance(x, px.QuantizedChunk)

    if int8:
        qx = px.as_quantized(x)
        m, n = qx.q.shape
        k = c.shape[0]
        assert fits(k, n), (k, n)
        csq = px.sqnorm(c)                  # full-width correction term
        cq, t = px.quantize_centroids(c, qx.scale)
        xs, cs = qx.q, cq
    else:
        m, n = x.shape
        k = c.shape[0]
        assert fits(k, n), (k, n)
        csq = px.sqnorm(c)                  # f32, from the full-width view
        store = px.storage_dtype(precision)
        xs, cs = x.astype(store), c.astype(store)

    block_m = min(block_m, max(8, m))
    bm = -(-m // block_m) * block_m
    k_pad, n_pad, block_k, block_n = _batched_tiles(k, n, block_k, block_n)

    xp = _pad_to(_pad_to(xs, bm, 0), n_pad, 1)
    cp = _pad_to(_pad_to(cs, k_pad, 0), n_pad, 1)
    nk = k_pad // block_k
    inputs = [xp, cp, _lane_tiles(csq, k_pad, block_k, value=_BIG)]
    if int8:
        inputs += [_lane_tiles(t, k_pad, block_k),
                   _pad_to(qx.scale[None, :], n_pad, 1)]

    sums_dtype = jnp.int32 if int8 else jnp.float32
    out_shape = [
        jax.ShapeDtypeStruct((k_pad, n_pad), sums_dtype),
        jax.ShapeDtypeStruct((1, k_pad), jnp.float32),
        jax.ShapeDtypeStruct((1, 1), jnp.float32),
    ]
    kw = dict(m=m, block_m=block_m, block_k=block_k, block_n=block_n,
              precision="int8" if int8 else precision)

    def whole(shape):
        """A block that is the whole array, at every grid step."""
        return pl.BlockSpec(shape, lambda *_: (0,) * len(shape))

    if pipeline == "dma":
        num_tiles = bm // block_m
        x_spec = [pl.BlockSpec(memory_space=pl.ANY)]
        aux_specs = [whole((k_pad, n_pad)), whole((nk, 1, block_k))]
        if int8:
            aux_specs += [whole((nk, 1, block_k)), whole((1, n_pad))]
        sums, counts, obj = pl.pallas_call(
            functools.partial(_fused_dma_kernel, num_tiles=num_tiles, **kw),
            in_specs=x_spec + aux_specs,
            out_specs=[whole((k_pad, n_pad)), whole((1, k_pad)),
                       whole((1, 1))],
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((2, block_m, n_pad), xp.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(*inputs)
    else:
        in_specs = [
            pl.BlockSpec((block_m, n_pad), lambda i: (i, 0)),
            whole((k_pad, n_pad)),
            whole((nk, 1, block_k)),
        ]
        if int8:
            in_specs += [whole((nk, 1, block_k)), whole((1, n_pad))]
        sums, counts, obj = pl.pallas_call(
            functools.partial(_fused_kernel, **kw),
            grid=(bm // block_m,),
            in_specs=in_specs,
            out_specs=[whole((k_pad, n_pad)), whole((1, k_pad)),
                       whole((1, 1))],
            out_shape=out_shape,
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(*inputs)

    if int8:
        # Exact int32 sums in the scaled space -> f32 sums in data space.
        sums_f = sums[:k, :n].astype(jnp.float32) * qx.scale[None, :]
        return sums_f, counts[0, :k], obj[0, 0]
    return sums[:k, :n], counts[0, :k], obj[0, 0]


def _fused_batched_kernel(*args, m: int, block_m: int, block_k: int,
                          block_n: int, precision: str):
    """One (batch, point-tile) grid cell of the batched fused step."""
    (x_ref, c_ref, csq_ref, t_ref, scale_ref, sums_ref, counts_ref, obj_ref,
     _) = _unpack_fused_refs(args, precision)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _zero():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        obj_ref[...] = jnp.zeros_like(obj_ref)

    _accumulate(
        i, x_ref[0], c_ref[0], csq_ref[0],
        None if t_ref is None else t_ref[0],
        None if scale_ref is None else scale_ref[0],
        sums_ref, counts_ref, obj_ref, lead=(0,), m=m, block_m=block_m,
        block_k=block_k, block_n=block_n, precision=precision)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_k", "block_n", "precision",
                     "interpret"),
)
def fused_step_batched_pallas(
    x,
    c: jax.Array,
    *,
    block_m: int = 256,
    block_k: int | None = None,
    block_n: int | None = None,
    precision: str = "f32",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x [B,m,n], c [B,k,n] -> (sums [B,k,n], counts [B,k], obj [B]).

    One ``pallas_call`` computes the per-chunk Lloyd statistics of all B
    streams: grid (B, m-tiles), with the batch as the outer grid dimension
    so each stream's accumulators are zeroed once and revisited in order.
    """
    px.check(precision)
    int8 = precision == "int8" or isinstance(x, px.QuantizedChunk)

    if int8:
        qx = px.as_quantized(x)
        batch, m, n = qx.q.shape
        k = c.shape[1]
        assert fits_batched(k, n), (k, n)
        csq = px.sqnorm(c)                  # [B, k] full-width
        cq, t = jax.vmap(px.quantize_centroids)(c, qx.scale)
        xs, cs = qx.q, cq
    else:
        batch, m, n = x.shape
        k = c.shape[1]
        assert fits_batched(k, n), (k, n)
        csq = px.sqnorm(c)                  # [B, k] f32, pre-cast view
        store = px.storage_dtype(precision)
        xs, cs = x.astype(store), c.astype(store)

    block_m = min(block_m, max(8, m))
    bm = -(-m // block_m) * block_m
    k_pad, n_pad, block_k, block_n = _batched_tiles(k, n, block_k, block_n)

    xp = _pad_to(_pad_to(xs, bm, 1), n_pad, 2)
    cp = _pad_to(_pad_to(cs, k_pad, 1), n_pad, 2)
    nk = k_pad // block_k

    def per_stream(shape):
        """Stream b's whole ``shape`` block, at every point tile."""
        return pl.BlockSpec((1,) + shape,
                            lambda b, i: (b,) + (0,) * len(shape))

    inputs = [xp, cp, _lane_tiles(csq, k_pad, block_k, value=_BIG)]
    tiles_spec = per_stream((nk, 1, block_k))
    in_specs = [
        pl.BlockSpec((1, block_m, n_pad), lambda b, i: (b, i, 0)),
        per_stream((k_pad, n_pad)),
        tiles_spec,
    ]
    if int8:
        inputs += [_lane_tiles(t, k_pad, block_k),
                   _pad_to(qx.scale[:, None, :], n_pad, 2)]
        in_specs += [tiles_spec, per_stream((1, n_pad))]

    sums_dtype = jnp.int32 if int8 else jnp.float32
    sums, counts, obj = pl.pallas_call(
        functools.partial(_fused_batched_kernel, m=m, block_m=block_m,
                          block_k=block_k, block_n=block_n,
                          precision="int8" if int8 else precision),
        grid=(batch, bm // block_m),
        in_specs=in_specs,
        out_specs=[per_stream((k_pad, n_pad)), per_stream((1, k_pad)),
                   per_stream((1, 1))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, k_pad, n_pad), sums_dtype),
            jax.ShapeDtypeStruct((batch, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((batch, 1, 1), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*inputs)

    if int8:
        sums_f = (sums[:, :k, :n].astype(jnp.float32)
                  * qx.scale[:, None, :])
        return sums_f, counts[:, 0, :k], obj[:, 0, 0]
    return sums[:, :k, :n], counts[:, 0, :k], obj[:, 0, 0]
