"""Jit'd public wrappers around the K-means kernels.

Dispatch policy
---------------
``impl='auto'`` resolves to the compiled Pallas kernel on TPU backends and to
the pure-jnp reference elsewhere (this container is CPU-only; Pallas runs
there in interpret mode, which we reserve for tests).  Every wrapper accepts
``impl`` overrides:

* ``'pallas'``            — compiled Pallas (TPU target)
* ``'pallas_interpret'``  — Pallas interpret mode (CPU correctness testing)
* ``'ref'``               — single-shot jnp oracle
* ``'ref_chunked'``       — jnp oracle, lax.map over point blocks (bounds the
                            [m,k] distance-matrix working set for big m)

Every wrapper also takes ``precision`` (``'auto'`` | ``'f32'`` | ``'bf16'``
| ``'bf16x3'`` | ``'int8'``, see :mod:`repro.kernels.precision`): the
storage/MXU element type of the point stream (``'auto'`` follows the data
dtype).  Accumulators, norms and the objective are always f32, so the knob
trades bytes/FLOP precision without touching acceptance semantics.  Under
``'int8'`` the chunk argument may be a pre-quantized
:class:`~repro.kernels.precision.QuantizedChunk` (int8 codes + per-feature
scales — what the streaming engine ships); plain arrays are quantized at
kernel entry with the same deterministic scheme.

Pallas launches consult :mod:`repro.kernels.autotune` for their tile sizes
(keyed by backend, batch, shape and precision) instead of hardcoded module
constants; with tuning disabled this returns the historical defaults.

Graceful degradation: a Pallas dispatch that raises demotes that
``(op, impl, shape, precision)`` to the ref path once per process (recorded
in :func:`kernel_demotions`, surfaced as a ``RuntimeWarning`` and as
``("kernel_fallback", ...)`` trace events by ``repro.api.fit``) — a kernel
bug degrades a long run instead of killing it.  A Pallas ``fused_step``
request whose ``(k, n)`` lies outside the fused kernel's envelope runs the
two-pass path and is recorded the same way, so an empty
:func:`kernel_demotions` means every Pallas request ran its Pallas kernel
(``chip_smoke.py`` fails on any entry).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels import precision as px
from repro.kernels.distance import assign_pallas
from repro.kernels.update import update_pallas

IMPLS = ("pallas", "pallas_interpret", "ref", "ref_chunked")
PRECISIONS = px.PRECISIONS

_DEFAULT_IMPL: str | None = None    # explicit override; None = auto-detect

# Graceful degradation: a Pallas dispatch that raises (lowering bug, tiling
# miss, backend quirk) demotes that (op, impl, shape, precision) to the ref
# path for the rest of the process — the run degrades instead of dying, and
# it happens ONCE per key, not once per chunk.  `kernel_demotions()` is the
# run-health surface (`repro.api.fit` turns new entries into
# ("kernel_fallback", ...) trace events).
_DEMOTIONS: dict[tuple, dict] = {}


def kernel_demotions() -> list[dict]:
    """Every Pallas→ref demotion this process has taken, in order."""
    return list(_DEMOTIONS.values())


def reset_kernel_demotions() -> None:
    """Forget recorded demotions (tests; a fixed backend mid-process)."""
    _DEMOTIONS.clear()


def record_demotion(op: str, impl: str, shape: tuple, precision: str,
                    exc: Exception) -> None:
    """Record a kernel failure observed *outside* the eager dispatch.

    Callers that run an op under their own ``jax.jit`` (the serving
    batcher) see Pallas failures escape at the outer compile, past the
    dispatch's try/except, so nothing demotes automatically.  When such a
    caller has classified the failure itself (e.g. repeated launch faults
    at one serving bucket), this records the same demotion the eager path
    would have taken: future eager dispatches at this key skip the Pallas
    path, and :func:`kernel_demotions` reflects it for run health.
    Idempotent per ``(op, impl, shape, precision)``.
    """
    if impl not in ("pallas", "pallas_interpret"):
        return
    key = (op, impl, tuple(shape), precision)
    if not _demoted(key):
        _demote(key, exc)


def _record_envelope_miss(op: str, impl: str, shape: tuple,
                          precision: str) -> None:
    """A Pallas request routed to the two-pass path by the envelope."""
    record_demotion(op, impl, shape, precision, ValueError(
        f"(k, n) = {shape[2:]} is outside the fused kernel envelope"))


def _demoted(key: tuple) -> bool:
    return key in _DEMOTIONS


def _demote(key: tuple, exc: Exception) -> None:
    op = key[0]
    _DEMOTIONS[key] = {
        "op": op,
        "impl": key[1],
        "shape": key[2],
        "precision": key[3],
        "error": f"{type(exc).__name__}: {exc}",
    }
    warnings.warn(
        f"pallas {op} dispatch failed for shape {key[2]} "
        f"({key[1]}, {key[3]}); demoting to the ref path for this process: "
        f"{exc}", RuntimeWarning, stacklevel=3)


def default_impl() -> str:
    """The impl ``'auto'`` resolves to: the explicit override if one was set
    via :func:`set_default_impl`, else a fresh backend probe (never cached,
    so backend changes between calls are picked up)."""
    if _DEFAULT_IMPL is not None:
        return _DEFAULT_IMPL
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def set_default_impl(impl: str | None) -> None:
    """Override what ``'auto'`` resolves to; ``None`` restores auto-detection."""
    if impl is not None and impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; known: {IMPLS}")
    global _DEFAULT_IMPL
    _DEFAULT_IMPL = impl


def resolve_impl(impl: str | None = "auto") -> str:
    """Resolve an ``impl`` knob to a concrete kernel implementation.

    This is the one resolver every dispatch site (and the ``repro.api``
    facade) routes through: ``'auto'``/``None`` resolve via
    :func:`default_impl`, concrete names are validated and passed through.
    """
    if impl is None or impl == "auto":
        return default_impl()
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r}; known: ('auto',) + {IMPLS}")
    return impl


def _tune_backend(impl: str) -> str:
    """Autotune cache partition: interpret timings never leak into compiled
    entries (and vice versa)."""
    return "interpret" if impl == "pallas_interpret" else jax.default_backend()


def _bench(x, factory):
    """The autotune bench factory, or None inside a jit trace.

    Most call sites sit under ``jax.jit`` (lloyd, the drivers), where the
    operands are tracers: timing there would measure trace time and block
    on abstract values.  The tuner then falls back to cached winners /
    defaults; eager warm-up (``repro.api.fit`` pre-tunes with concrete
    arrays) is what populates the cache.
    """
    arr = x.q if isinstance(x, px.QuantizedChunk) else x
    return None if isinstance(arr, jax.core.Tracer) else factory


def assign(
    x: jax.Array,
    c: jax.Array,
    *,
    impl: str = "auto",
    precision: str = "auto",
    chunk: int = 65536,
) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment.  x [m,n], c [k,n] -> (ids i32 [m], d f32 [m])."""
    impl = resolve_impl(impl)
    precision = px.resolve(precision, x.dtype)
    if impl in ("pallas", "pallas_interpret"):
        dkey = ("assign", impl, (1, x.shape[0], c.shape[0], x.shape[1]),
                precision)
        if not _demoted(dkey):
            try:
                interp = impl == "pallas_interpret"
                blocks = autotune.get_blocks(
                    "assign",
                    _bench(x, lambda blk: lambda: jax.block_until_ready(
                        assign_pallas(x, c, precision=precision,
                                      interpret=interp, **blk))),
                    backend=_tune_backend(impl), b=1, m=x.shape[0],
                    k=c.shape[0], n=x.shape[1], precision=precision)
                return assign_pallas(x, c, precision=precision,
                                     interpret=interp, **blocks)
            except Exception as exc:
                _demote(dkey, exc)
        impl = "ref"                    # demoted shape: ref path below
    if impl == "ref":
        return ref.assign_ref(x, c, precision=precision)
    if impl == "ref_chunked":
        return _assign_chunked(x, c, chunk=chunk, precision=precision)
    raise ValueError(f"unknown impl {impl!r}")


@functools.partial(jax.jit, static_argnames=("chunk", "precision"))
def _assign_chunked(x, c, *, chunk, precision="f32"):
    m = x.shape[0]
    if m <= chunk:
        return ref.assign_ref(x, c, precision=precision)
    nblk = -(-m // chunk)
    pad = nblk * chunk - m
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xb = xp.reshape(nblk, chunk, x.shape[1])
    ids, d = jax.lax.map(
        lambda xi: ref.assign_ref(xi, c, precision=precision), xb)
    return ids.reshape(-1)[:m], d.reshape(-1)[:m]


def warm_assign(
    m: int,
    k: int,
    n: int,
    *,
    impl: str = "auto",
    precision: str = "auto",
    dtype=jnp.float32,
) -> str:
    """Eagerly exercise the :func:`assign` dispatch at a concrete shape.

    Callers that run ``assign`` under their own ``jax.jit`` (the serving
    batcher, ``lloyd``'s epilogue) never hit the eager machinery: under a
    trace the autotune bench cannot time (``_bench`` returns None) and a
    Pallas *compile* failure surfaces at the outer jit's compile time —
    outside :func:`assign`'s try/except, so nothing demotes and the caller
    crashes.  ``fit()`` solves this for ``fused_step`` by pre-tuning with
    concrete arrays; this is the same move packaged for bare ``assign``:
    one cheap eager call at ``(m, k, n)`` consults/populates the autotune
    cache and, if the Pallas build fails, demotes exactly this
    serving-shaped key to the ref path — off the request path, once.

    Returns the impl the shape will actually run after warmup
    (``'ref'`` when the Pallas path demoted).
    """
    impl = resolve_impl(impl)
    x = jnp.zeros((m, n), dtype)
    c = jnp.zeros((k, n), dtype)
    prec = px.resolve(precision, x.dtype)
    jax.block_until_ready(assign(x, c, impl=impl, precision=prec))
    if impl in ("pallas", "pallas_interpret") and _demoted(
            ("assign", impl, (1, m, k, n), prec)):
        return "ref"
    return impl


def update(
    x: jax.Array,
    ids: jax.Array,
    k: int,
    *,
    weights: jax.Array | None = None,
    impl: str = "auto",
    precision: str = "auto",
) -> tuple[jax.Array, jax.Array]:
    """Cluster sums/counts.  x [m,n], ids [m] -> (sums [k,n], counts [k])."""
    impl = resolve_impl(impl)
    precision = px.resolve(precision, x.dtype)
    if weights is not None:
        # Weighted path stays on the jnp oracle (cold path: coresets, K-means||).
        return ref.update_ref(x, ids, k, weights, precision=precision)
    if impl in ("pallas", "pallas_interpret"):
        dkey = ("update", impl, (1, x.shape[0], k, x.shape[1]), precision)
        if not _demoted(dkey):
            try:
                return update_pallas(x, ids, k, precision=precision,
                                     interpret=impl == "pallas_interpret")
            except Exception as exc:
                _demote(dkey, exc)
        impl = "ref"                    # demoted shape: ref path below
    if impl in ("ref", "ref_chunked"):
        return ref.update_ref(x, ids, k, precision=precision)
    raise ValueError(f"unknown impl {impl!r}")


def assign_and_update(
    x: jax.Array,
    c: jax.Array,
    *,
    weights: jax.Array | None = None,
    impl: str = "auto",
    precision: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused Lloyd step's statistics: (ids, d, sums, counts)."""
    ids, d = assign(x, c, impl=impl, precision=precision)
    sums, counts = update(x, ids, c.shape[0], weights=weights, impl=impl,
                          precision=precision)
    return ids, d, sums, counts


def fused_step(
    x: jax.Array,
    c: jax.Array,
    *,
    weights: jax.Array | None = None,
    impl: str = "auto",
    precision: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One Lloyd iteration's (sums, counts, objective) — single-HBM-pass
    Pallas kernel when the (k, n) envelope fits, two-pass fallback
    otherwise."""
    from repro.kernels import fused_step as fused

    impl = resolve_impl(impl)
    precision = px.resolve(precision, x.dtype)
    k, n = c.shape[0], c.shape[1]
    if weights is None and not fused.fits(k, n):
        _record_envelope_miss("fused", impl, (1, x.shape[0], k, n), precision)
    elif weights is None:
        if impl in ("pallas", "pallas_interpret"):
            dkey = ("fused", impl, (1, x.shape[0], k, n), precision)
            if not _demoted(dkey):
                try:
                    interp = impl == "pallas_interpret"
                    blocks = autotune.get_blocks(
                        "fused",
                        _bench(x, lambda blk: lambda: jax.block_until_ready(
                            fused.fused_step_pallas(
                                x, c, precision=precision, interpret=interp,
                                **blk))),
                        backend=_tune_backend(impl), b=1, m=x.shape[0], k=k,
                        n=n, precision=precision)
                    return fused.fused_step_pallas(
                        x, c, precision=precision, interpret=interp, **blocks)
                except Exception as exc:
                    _demote(dkey, exc)
            # demoted shape: the two-pass ref fallback below
    # Two-pass fallback (non-fused impls, weighted steps, or an envelope
    # miss).  Explicit ref impls are honored as-is — in particular
    # 'ref_chunked' keeps its bounded [chunk, k] distance working set for
    # big m — while the Pallas impls fall back to the plain oracle.
    fallback = impl if impl.startswith("ref") else "ref"
    ids, d = assign(x, c, impl=fallback, precision=precision)
    sums, counts = update(x, ids, k, weights=weights, impl=fallback,
                          precision=precision)
    obj = jnp.sum(d * weights) if weights is not None else jnp.sum(d)
    return sums, counts, obj


@functools.partial(jax.jit, static_argnames=("precision",))
def _fused_step_batched_ref(x, c, *, precision="f32"):
    """Batched two-pass oracle.

    ``lax.map`` over streams, not ``vmap``: the math per stream is
    identical (streams are independent), but mapping keeps each stream's
    [m, k] distance working set cache-resident on CPU, where the vmapped
    [B, m, k] intermediates are ~2.5x slower at paper-scale chunks.  The
    Pallas path gets its batch parallelism from the kernel grid instead.
    """

    def one(xc):
        xb, cb = xc
        ids, d = ref.assign_ref(xb, cb, precision=precision)
        sums, counts = ref.update_ref(xb, ids, cb.shape[0],
                                      precision=precision)
        return sums, counts, jnp.sum(d)

    return jax.lax.map(one, (x, c))


def fused_step_batched(
    x: jax.Array,
    c: jax.Array,
    *,
    impl: str = "auto",
    precision: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """B concurrent Lloyd iterations in one launch.

    x [B,m,n], c [B,k,n] -> (sums [B,k,n], counts [B,k], obj [B]).  Routes
    to the batched fused Pallas kernel inside its (wider, k/n-tiled)
    envelope; falls back to :func:`_fused_step_batched_ref` elsewhere — a
    ``lax.map`` (not ``vmap``) over the two-pass jnp oracle, which keeps
    each stream's [m, k] distance working set cache-resident on CPU (the
    vmapped [B, m, k] intermediates measured ~2.5x slower at paper-scale
    chunks; see its docstring).
    """
    from repro.kernels import fused_step as fused

    impl = resolve_impl(impl)
    precision = px.resolve(precision, x.dtype)
    batch, m = x.shape[0], x.shape[1]
    k, n = c.shape[1], c.shape[2]
    if not fused.fits_batched(k, n):
        _record_envelope_miss("fused_batched", impl, (batch, m, k, n),
                              precision)
    elif impl in ("pallas", "pallas_interpret"):
        dkey = ("fused_batched", impl, (batch, m, k, n), precision)
        if not _demoted(dkey):
            try:
                interp = impl == "pallas_interpret"
                blocks = autotune.get_blocks(
                    "fused_batched",
                    _bench(x, lambda blk: lambda: jax.block_until_ready(
                        fused.fused_step_batched_pallas(
                            x, c, precision=precision, interpret=interp,
                            **blk))),
                    backend=_tune_backend(impl), b=batch, m=m, k=k, n=n,
                    precision=precision)
                return fused.fused_step_batched_pallas(
                    x, c, precision=precision, interpret=interp, **blocks)
            except Exception as exc:
                _demote(dkey, exc)
            # demoted shape: the batched two-pass oracle below
    return _fused_step_batched_ref(x, c, precision=precision)
