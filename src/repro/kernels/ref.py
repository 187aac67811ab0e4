"""Pure-jnp oracles for the Pallas kernels.

These are the semantic ground truth: every Pallas kernel in this package has
an ``*_ref`` twin here and tests assert allclose between the two across shape
and dtype sweeps. They are also the production path on non-TPU backends.

All oracles take a ``precision`` knob (see :mod:`repro.kernels.precision`):
``None`` infers it from the data dtype (bf16 arrays contract in bf16, the
historical behaviour), a concrete value forces the policy.  Accumulation —
norms, sums, counts, objective — is always float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import precision as px


def pairwise_sqdist_ref(x, c: jax.Array,
                        x2: jax.Array | None = None,
                        *, precision: str | None = None) -> jax.Array:
    """Squared euclidean distances between rows of x [m,n] and c [k,n] -> [m,k].

    The dominant matmul runs under the ``precision`` policy (bf16 data at
    half the bytes, optional bf16x3 compensation); ``||x||^2`` / ``||c||^2``
    are always f32, ``||x||^2`` taken of the points in their storage dtype
    (what the Pallas kernels stream) and ``||c||^2`` of the full-width
    centroids.  ``x2`` (optional [m,1]) lets callers hoist the point
    norms out of loops that probe many candidate centroid sets (K-means++
    seeding reads the chunk once per slot instead of twice).

    Under ``'int8'`` (or when ``x`` arrives as a
    :class:`~repro.kernels.precision.QuantizedChunk`) the contraction is the
    int8 x int8 -> int32 scheme of :mod:`repro.kernels.precision`: per-feature
    chunk scales, centroids re-quantized in the scaled space with per-row
    scales, and the f32 norm correction term (``||c||^2`` full-width,
    ``||x||^2`` from the dequantized codes)."""
    prec = px.from_dtype(x.dtype) if precision is None else px.check(precision)
    if prec == "int8":
        qx = px.as_quantized(x)
        cq, t = px.quantize_centroids(c, qx.scale)
        if x2 is None:
            x2 = px.sqnorm(px.dequantize(qx), keepdims=True)
        c2 = px.sqnorm(c)[None, :]
        idots = px.intdot(qx.q, cq, (((1,), (1,)), ((), ())))   # [m,k] i32
        dots = idots.astype(jnp.float32) * t[None, :]
        # Associate as (c2 - 2 dots) + x2: the order the Pallas kernels use
        # (score assembled per k-tile, ||x||^2 added at the end), so oracle
        # and kernel agree bitwise, not just to rounding.
        return jnp.maximum((c2 - 2.0 * dots) + x2, 0.0)
    if x2 is None:
        # ||x||^2 of the stored points, as the kernels see them.
        x2 = px.sqnorm(x.astype(px.storage_dtype(prec)), keepdims=True)
    c2 = px.sqnorm(c)[None, :]
    dots = px.dot(x, c, (((1,), (1,)), ((), ())), prec)
    d = x2 - 2.0 * dots + c2
    return jnp.maximum(d, 0.0)


def assign_ref(x, c: jax.Array,
               *, precision: str | None = None) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment.

    Returns (ids int32 [m], sq_dist f32 [m]).
    """
    d = pairwise_sqdist_ref(x, c, precision=precision)
    ids = jnp.argmin(d, axis=1).astype(jnp.int32)
    mind = jnp.min(d, axis=1)
    return ids, mind


def update_ref(
    x,
    ids: jax.Array,
    k: int,
    weights: jax.Array | None = None,
    *,
    precision: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Centroid-update statistics: per-cluster feature sums and counts.

    Returns (sums f32 [k,n], counts f32 [k]).  ``ids`` entries outside
    [0, k) contribute nothing (used for padding).  bf16 data is read at
    half bytes; accumulation stays fp32 (one-hot entries are 0/1, exactly
    representable in bf16, so the membership operand loses nothing).

    Under ``'int8'`` the unweighted one-hot is 0/1 — int8-exact — so the
    sums contraction is onehot x codes in int32 (exact), scaled by the
    per-feature chunk scales afterwards.  A weighted update has non-integer
    membership and falls back to f32 math on the dequantized codes (cold
    path: only baselines weight updates).
    """
    prec = px.from_dtype(x.dtype) if precision is None else px.check(precision)
    if prec == "int8":
        qx = px.as_quantized(x)
        if weights is not None:
            return update_ref(px.dequantize(qx), ids, k, weights,
                              precision="f32")
        onehot = jax.nn.one_hot(ids, k, dtype=jnp.int8)       # [m,k]; 0/1
        isums = px.intdot(onehot, qx.q, (((0,), (0,)), ((), ())))  # [k,n] i32
        sums = isums.astype(jnp.float32) * qx.scale[None, :]
        counts = jnp.sum(onehot.astype(jnp.float32), axis=0)
        return sums, counts
    onehot = jax.nn.one_hot(ids, k, dtype=jnp.float32)     # [m,k]; oob -> 0s
    if weights is not None:
        onehot = onehot * weights.astype(jnp.float32)[:, None]
    sums = px.dot(onehot, x, (((0,), (0,)), ((), ())), prec)  # [k,n] f32
    counts = jnp.sum(onehot, axis=0)                          # [k]
    return sums, counts


def min_update_ref(d: jax.Array, x: jax.Array, c_new: jax.Array) -> jax.Array:
    """K-means++ distance relaxation: d <- min(d, ||x - c_new||^2).

    d [m], x [m,n], c_new [n] -> [m].
    """
    x = x.astype(jnp.float32)
    c_new = c_new.astype(jnp.float32)
    diff = x - c_new[None, :]
    d_new = jnp.sum(diff * diff, axis=-1)
    return jnp.minimum(d, d_new)
