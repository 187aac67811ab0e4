"""Mixed-precision policy for the K-means kernel stack.

One ``precision`` knob is threaded through every kernel, oracle and driver:

* ``'f32'``    — everything float32 (the historical behaviour).
* ``'bf16'``   — inputs are *stored and streamed* as bfloat16 (half the HBM /
  host->device bytes of the bandwidth-bound chunk loop) and the distance /
  update contractions run bf16 x bf16 on the MXU.  Everything that decides
  or compares — accumulators, ``||c||^2`` / ``||x||^2`` norms, the objective,
  centroid updates, ``f_best`` acceptance — stays float32 via
  ``preferred_element_type``.
* ``'bf16x3'`` — compensated compute: operands stay f32 in storage and every
  contraction is decomposed into three bf16 products
  (``a.b ~= hi_a.hi_b + hi_a.lo_b + lo_a.hi_b`` with ``hi = bf16(a)``,
  ``lo = bf16(a - hi)``), recovering near-f32 accuracy at bf16 MXU rates.
  No bandwidth saving — it is a compute-precision option, used e.g. for the
  objective epilogue when bf16 rounding of f(C, X) itself is the concern.
* ``'int8'``   — chunk data is quantized once per chunk to int8 with
  per-feature scales (``s[f] = max_m |x[m,f]| / 127``) and streamed as a
  :class:`QuantizedChunk` at a quarter of the f32 bytes.  Centroids are
  re-quantized per Lloyd iteration *in the scaled feature space* with
  per-row scales ``t[j]`` so the distance contraction is a pure
  int8 x int8 -> int32 MXU matmul whose scale factors out per output
  column: ``x.c_j ~= (sum_f xq cq) * t[j]``.  The norm terms ``||c||^2``
  (full-width) and ``||x||^2`` (from the dequantized representation) stay
  f32 — the *correction term* that keeps distances honest.  As with bf16,
  the ``f_best`` acceptance objective is never evaluated through the
  quantized contraction: drivers keep a full-width copy for the epilogue
  (the bf16 f_best lesson, below).

The bf16 f_best lesson: ``||x||^2 - 2 x.c + ||c||^2`` cancels
catastrophically near the optimum, and the 0-clamp turns rounding noise
into a one-sided bias, so acceptance comparisons evaluated through reduced
contractions drift (~2.4% observed for bf16).  Every reduced-precision
policy therefore evaluates the accepting objective with f32 contractions;
a <1% drift test enforces it per policy.

The helpers here are pure jnp/lax so they are usable both from the jnp
oracles and *inside* Pallas kernel bodies.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "bf16", "bf16x3", "int8")

INT8_MAX = 127.0

# Smallest admissible quantization scale: guards the x/s division against
# all-zero features (warm-up zeros, constant columns) without perturbing any
# real scale (float32 tiny is ~1e-38).
_SCALE_FLOOR = 1e-30


def check(precision: str) -> str:
    """Validate and return a *concrete* ``precision``."""
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; known: {PRECISIONS}")
    return precision


def from_dtype(dtype) -> str:
    """The precision a raw array dtype implies (dtype-driven ``'auto'``)."""
    if dtype == jnp.bfloat16:
        return "bf16"
    if dtype == jnp.int8:
        return "int8"
    return "f32"


def resolve(precision: str | None, dtype) -> str:
    """Resolve a precision knob against the data dtype.

    ``'auto'`` / ``None`` follow the data (bf16 arrays contract in bf16, the
    historical behaviour; everything else is f32); concrete values are
    authoritative — ``'f32'`` up-casts bf16 data to full width, ``'bf16'``
    down-casts f32 storage.
    """
    if precision is None or precision == "auto":
        return from_dtype(dtype)
    return check(precision)


def storage_dtype(precision: str):
    """The dtype chunk data is stored/streamed in under a concrete policy.

    For ``'int8'`` the payload is a :class:`QuantizedChunk` (int8 codes +
    f32 per-feature scales); this returns the code dtype.
    """
    check(precision)
    if precision == "bf16":
        return jnp.bfloat16
    if precision == "int8":
        return jnp.int8
    return jnp.float32


def cast_storage(x, precision: str | None):
    """Cast data to its storage form under ``precision`` (auto-aware).

    Returns a plain array for the float policies and a
    :class:`QuantizedChunk` for ``'int8'`` (already-quantized input passes
    through unchanged).
    """
    if isinstance(x, QuantizedChunk):
        return x
    if resolve(precision, x.dtype) == "int8":
        return quantize_chunk(x)
    return x.astype(storage_dtype(resolve(precision, x.dtype)))


def host_dtype(precision: str | None):
    """The NumPy dtype a host-side chunk cast should request, or ``None``.

    ``'bf16'`` asks for ``ml_dtypes.bfloat16`` (a jax dependency;
    ``jax.device_put`` of such an array yields a device bf16 buffer with no
    further conversion) so the cast happens on the host and host->device
    transfers move half the bytes.  Every other policy returns ``None`` —
    "no explicit request", letting each data source serve its native
    dtype.
    """
    if precision == "bf16":
        import ml_dtypes
        import numpy as np

        return np.dtype(ml_dtypes.bfloat16)
    return None


def _split_bf16(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def dot(a: jax.Array, b: jax.Array, dimension_numbers, precision: str):
    """``lax.dot_general`` under the mixed-precision policy.

    Always accumulates and returns float32 (``preferred_element_type``); the
    knob only controls the operand element type fed to the MXU.  Under
    ``'bf16x3'``, operands that arrive as bf16 carry no low bits, so the
    compensation degrades gracefully to the plain bf16 product.
    """
    check(precision)
    if precision == "int8":
        raise ValueError(
            "px.dot has no generic int8 path: the per-feature/per-row scale "
            "algebra is contraction-specific. Use quantize_chunk / "
            "quantize_centroids / intdot explicitly (see ref.py oracles).")
    dg = lambda x, y, p=None: jax.lax.dot_general(  # noqa: E731
        x, y, dimension_numbers, precision=p,
        preferred_element_type=jnp.float32)
    if precision == "f32":
        # HIGHEST: XLA:TPU's DEFAULT for f32 operands is one bf16 pass,
        # which is the cancellation the f32 policy exists to avoid.  The
        # CPU computes full f32 either way.
        return dg(a.astype(jnp.float32), b.astype(jnp.float32),
                  jax.lax.Precision.HIGHEST)
    if precision == "bf16":
        return dg(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return dg(ah, bh) + dg(ah, bl) + dg(al, bh)


def sqnorm(a: jax.Array, axis=-1, keepdims: bool = False) -> jax.Array:
    """``sum(a*a)`` in f32 regardless of storage dtype (norms never bf16)."""
    a = a.astype(jnp.float32)
    return jnp.sum(a * a, axis=axis, keepdims=keepdims)


# ---------------------------------------------------------------------------
# int8 quantization scheme
# ---------------------------------------------------------------------------
#
# Chunk side (once per chunk, on host or at Lloyd entry):
#   s[f]  = max_m |x[m, f]| / 127          (per-feature, clamped away from 0)
#   xq    = round(x / s) in [-127, 127]    (int8 codes)
# Centroid side (per Lloyd iteration, cheap: k rows):
#   cs    = c * s                          (centroids in the scaled space)
#   t[j]  = max_f |cs[j, f]| / 127         (per-row, clamped)
#   cq    = round(cs / t) in [-127, 127]
# Then the distance contraction factors exactly per output column:
#   x . c_j  ~=  (sum_f xq[m,f] cq[j,f]) * t[j]        (int8 matmul -> int32)
# and ||x||^2 / ||c||^2 stay f32 (the correction term): ||c||^2 from the
# full-width centroids, ||x||^2 from the dequantized codes (the values the
# contraction actually sees), so the assembled distance is the honest
# distance of the quantized representation — bitwise reproducible between
# the jnp oracle and the Pallas kernel on integer data.


class QuantizedChunk(NamedTuple):
    """An int8-quantized chunk: codes plus per-feature scales.

    ``q`` is int8 ``[..., m, n]``; ``scale`` is f32 ``[..., n]`` (one scale
    per feature, broadcast over points; batched chunks carry one scale row
    per stream).  NamedTuples are jax pytrees, so a QuantizedChunk passes
    through ``jit`` / ``lax.map`` / ``device_put`` like an array pair.
    """

    q: jax.Array
    scale: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def ndim(self):
        return self.q.ndim


def feature_scales(x: jax.Array, axis: int = -2) -> jax.Array:
    """Per-feature quantization scales ``max|x|/127`` over the points axis."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis)
    return jnp.maximum(absmax / INT8_MAX, _SCALE_FLOOR)


def quantize_chunk(x: jax.Array) -> "QuantizedChunk":
    """Quantize a chunk ``[..., m, n]`` to int8 codes + per-feature scales."""
    x = x.astype(jnp.float32)
    scale = feature_scales(x)                                 # [..., n]
    q = jnp.clip(jnp.round(x / scale[..., None, :]), -INT8_MAX, INT8_MAX)
    return QuantizedChunk(q.astype(jnp.int8), scale)


def as_quantized(x) -> "QuantizedChunk":
    """Coerce a chunk to its quantized form (idempotent)."""
    return x if isinstance(x, QuantizedChunk) else quantize_chunk(x)


def dequantize(qx: "QuantizedChunk") -> jax.Array:
    """Reconstruct the f32 values the int8 contraction actually sees."""
    return qx.q.astype(jnp.float32) * qx.scale[..., None, :]


def quantize_centroids(c: jax.Array,
                       scale: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantize centroids ``[k, n]`` into the chunk's scaled feature space.

    Returns ``(cq int8 [k, n], t f32 [k])`` with
    ``c[j] . x[m] ~= (cq[j] . xq[m]) * t[j]`` — the per-row scale ``t``
    factors out of the int8 contraction per output column.
    """
    cs = c.astype(jnp.float32) * scale[None, :]               # scaled space
    t = jnp.maximum(jnp.max(jnp.abs(cs), axis=-1) / INT8_MAX, _SCALE_FLOOR)
    cq = jnp.clip(jnp.round(cs / t[:, None]), -INT8_MAX, INT8_MAX)
    return cq.astype(jnp.int8), t


def intdot(a: jax.Array, b: jax.Array, dimension_numbers) -> jax.Array:
    """int8 x int8 ``dot_general`` accumulating in int32 (exact).

    With ``|q| <= 127`` a product is at most 16129, so contractions up to
    ~133k elements fit int32 — far beyond any feature width here.
    """
    return jax.lax.dot_general(
        a.astype(jnp.int8), b.astype(jnp.int8), dimension_numbers,
        preferred_element_type=jnp.int32)


def host_quantize(arr) -> tuple:
    """NumPy twin of :func:`quantize_chunk` for the host prefetch thread.

    Returns ``(q int8 [m, n], scale f32 [n])`` computed with the same
    round-half-to-even semantics, so host-quantized and device-quantized
    chunks are bitwise identical.  Shipping int8 codes + one f32 scale row
    moves ~a quarter of the f32 host->device bytes.
    """
    import numpy as np

    arr = np.asarray(arr, dtype=np.float32)
    scale = np.maximum(np.abs(arr).max(axis=-2) / INT8_MAX, _SCALE_FLOOR)
    scale = scale.astype(np.float32)
    q = np.clip(np.round(arr / scale[..., None, :]), -INT8_MAX, INT8_MAX)
    return q.astype(np.int8), scale
