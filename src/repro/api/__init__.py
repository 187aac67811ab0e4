"""`repro.api` — the single public entry point for Big-means clustering.

One config (:class:`BigMeansConfig`), one :func:`fit`, pluggable data
sources and driver strategies::

    from repro.api import fit

    result = fit(X, k=25, s=16384, n_chunks=100)          # auto strategy
    result = fit(X, cfg, method="batched")                # explicit strategy
    result = fit("data.npy", cfg, method="streaming")     # out-of-core
    result = fit(X, cfg, method="kmeanspp")               # §5 baseline

Every call returns a :class:`FitResult` — Big-means strategies and §5
baselines alike — so algorithms are compared through one interface.  The
low-level drivers (``repro.core.bigmeans``, ``repro.cluster.runner``) stay
importable, but documented usage goes through this facade.
"""
from __future__ import annotations

import contextlib
import time

import jax
from jax.profiler import TraceAnnotation

from repro import spans
from repro.api import baselines as baselines
from repro.api import sources as sources
from repro.api import strategies as strategies
from repro.api.baselines import get_baseline, list_baselines, register_baseline
from repro.api.config import BigMeansConfig
from repro.api.result import FitResult
from repro.api.sources import (
    ArraySource,
    DataSource,
    IteratorSource,
    MemmapSource,
    ProviderSource,
    as_source,
)
from repro.api.strategies import (
    get_strategy,
    list_strategies,
    register_strategy,
    resolve_auto,
)
from repro.cluster.runner import EndOfStream

# The declarative execution-placement spec (BigMeansConfig.topology) is part
# of the public fitting surface.
from repro.engine.topology import TopologySpec

# Synthetic-data helpers re-exported so examples and smoke tests can run off
# `repro.api` imports alone.
from repro.data import synthetic as synthetic

# The assignment-serving subsystem (see repro.serve): training produces the
# centroids, serve() is how their value is realized at assignment time.
from repro.serve import ServeConfig, Server, serve

__all__ = [
    "ArraySource",
    "BigMeansConfig",
    "DataSource",
    "EndOfStream",
    "FitResult",
    "IteratorSource",
    "MemmapSource",
    "ProviderSource",
    "as_source",
    "baselines",
    "evaluate",
    "fit",
    "get_baseline",
    "get_strategy",
    "list_baselines",
    "list_methods",
    "list_strategies",
    "lower_fit",
    "register_baseline",
    "register_strategy",
    "resolve_auto",
    "serve",
    "ServeConfig",
    "Server",
    "TopologySpec",
    "sources",
    "strategies",
    "synthetic",
]


def _pretune(cfg: BigMeansConfig, source) -> None:
    """Populate the autotune cache eagerly, off the jit path.

    The drivers call the kernels from inside ``jax.jit``, where operands
    are tracers and timing is impossible — so tuning happens here, once,
    with concrete arrays at the exact hot-path shapes this fit will launch
    (single fused step at [s, n], batched step at [batch, s, n], and the
    epilogue assignment).  Compiled-Pallas only: interpret mode is a CPU
    correctness harness whose timings would be meaningless.
    """
    from repro.kernels import ops
    from repro.kernels import precision as px

    impl = cfg.resolved_impl()
    if impl != "pallas":
        return
    import jax.numpy as jnp

    # Resolve 'auto' against the data dtype when the source exposes one
    # (in-core arrays/memmaps); streamed chunks arrive f32 unless bf16 is
    # explicitly requested, so f32 is the right fallback.
    data_dtype = getattr(getattr(source, "X", None), "dtype", None) \
        or getattr(getattr(source, "mm", None), "dtype", None) or jnp.float32
    prec = px.resolve(cfg.precision, data_dtype)
    kx, kc = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (cfg.s, source.n_features), jnp.float32)
    c = jax.random.normal(kc, (cfg.k, source.n_features), jnp.float32)
    x_full = x
    x = px.cast_storage(x, prec)
    ops.fused_step(x, c, impl=impl, precision=prec)
    ops.assign(x, c, impl=impl, precision=prec)
    if prec in ("bf16", "int8"):
        # lloyd's objective epilogue assigns with f32 contractions on the
        # full-width view (see core/kmeans.py) — tune that key too, or it
        # runs untuned defaults.
        ops.assign(x_full, c, impl=impl, precision="f32")
    if cfg.batch > 1:
        if isinstance(x, px.QuantizedChunk):
            xb = px.QuantizedChunk(
                q=jnp.broadcast_to(x.q, (cfg.batch,) + x.q.shape),
                scale=jnp.broadcast_to(x.scale, (cfg.batch,) + x.scale.shape))
        else:
            xb = jnp.broadcast_to(x, (cfg.batch,) + x.shape)
        cb = jnp.broadcast_to(c, (cfg.batch,) + c.shape)
        ops.fused_step_batched(xb, cb, impl=impl, precision=prec)


def list_methods() -> list[str]:
    """Everything :func:`fit` accepts as ``method``."""
    return ["auto"] + list_strategies() + list_baselines()


def _resolve_method(method: str):
    if method == "auto" or method in list_strategies():
        return get_strategy(method)
    if method in list_baselines():
        return get_baseline(method)
    raise KeyError(f"unknown method {method!r}; known: {list_methods()}")


def _resolve_config(config: BigMeansConfig | None,
                    overrides: dict) -> BigMeansConfig:
    if config is None:
        missing = {"k", "s"} - set(overrides)
        if missing:
            raise TypeError(
                f"fit() without a config needs {sorted(missing)} "
                "(e.g. fit(X, k=25, s=16384))")
        return BigMeansConfig(**overrides)
    return config.replace(**overrides) if overrides else config


def _resolve_source(cfg: BigMeansConfig, data, n_features: int | None):
    from repro.engine import topology as topo_lib

    if topo_lib.requested_kind(cfg) == "host_mesh":
        # jax.distributed.initialize() must run before the first JAX
        # computation in the process (the PRNG key below already is one),
        # so multi-host configs bootstrap the process group here.
        # Idempotent: resolve() reuses an already-initialized group.
        topo_lib.resolve(cfg.topology)
    return as_source(data, n_features=n_features)


@contextlib.contextmanager
def _tuning(cfg: BigMeansConfig, source):
    """Scoped to one call (exception paths included): with
    ``cfg.autotune`` the tuner times candidate kernel tilings for this
    fit's shapes eagerly (off the jit path) and caches the winners (see
    repro.kernels.autotune); results are unaffected.  The previous enable
    state is restored afterwards so a later fit with autotune=False never
    pays surprise timing sweeps."""
    if not cfg.autotune:
        yield
        return
    from repro.kernels import autotune

    prev = autotune.enabled()
    autotune.enable(True)
    try:
        _pretune(cfg, source)
        yield
    finally:
        autotune.enable(prev)


def fit(
    data,
    config: BigMeansConfig | None = None,
    *,
    method: str = "auto",
    key: jax.Array | None = None,
    n_features: int | None = None,
    **overrides,
) -> FitResult:
    """Cluster ``data`` and return a :class:`FitResult`.

    * ``data`` — anything :func:`as_source` accepts: a 2-D array, an
      ``.npy`` path, a ``provider(chunk_id)`` callable, a chunk iterator,
      or a :class:`DataSource`.
    * ``config`` — a :class:`BigMeansConfig`; ``overrides`` are applied on
      top (or, with no config, must include at least ``k`` and ``s``).
    * ``method`` — a strategy (``auto`` / ``sequential`` / ``batched`` /
      ``sharded`` / ``streaming``) or a §5 baseline (see
      :func:`list_methods`).
    * ``key`` — PRNG key; defaults to ``PRNGKey(config.seed)``.
    * ``n_features`` — feature count, only needed for provider/iterator
      data whose first chunk should not be probed eagerly.

    ``wall_time_s`` on the result covers the whole call, compile included.
    A profiler capture shows the call as the host spans
    ``repro.fit.dispatch`` (until the jitted call returns; where the fit
    runs as one program, its args say how chunk rows are gathered and
    which Lloyd path runs) and
    ``repro.fit.collect`` (reading its result), and its device operations
    under the ``repro.fit.*`` scopes (see :mod:`repro.spans`).
    """
    cfg = _resolve_config(config, overrides)
    from repro.kernels import autotune as _autotune
    from repro.kernels import ops as _ops

    with TraceAnnotation(spans.FIT_DISPATCH, strategy=method,
                         n_chunks=cfg.n_chunks) as span:
        source = _resolve_source(cfg, data, n_features)
        # Snapshot before any kernel work: the disk cache loads lazily on
        # the first get_blocks lookup, which may happen inside _pretune.
        n_tune_events = len(_autotune.events())
        with _tuning(cfg, source):
            fn = _resolve_method(method)
            if key is None:
                key = jax.random.PRNGKey(cfg.seed)
            n_demotions = len(_ops.kernel_demotions())
            t0 = time.monotonic()
            program = strategies.plan(method, cfg, source, key)
            if program is None:
                result = fn(cfg, source, key)
            else:
                span.set_metadata(**program.dispatch_args())
                out = program.dispatch()
    if program is None:
        jax.block_until_ready(result.centroids)
    else:
        result = strategies.collect(program, out)
        if method == "auto":
            result.extras["auto"] = True
    result.wall_time_s = time.monotonic() - t0
    # Graceful kernel degradation taken during this call surfaces on
    # the result: trace events + the run-health summary.
    fallbacks = _ops.kernel_demotions()[n_demotions:]
    for d in fallbacks:
        result.trace.append(("kernel_fallback", d["op"], d["error"]))
    # Likewise for autotune-cache files that were ignored (corrupt or
    # stale schema): never fatal, but never silent either.
    for ev in _autotune.events()[n_tune_events:]:
        result.trace.append(ev)
    if fallbacks:
        result.extras.setdefault("health", {})["kernel_fallbacks"] = \
            fallbacks
    # Suite hook: how this fit was actually dispatched, in one
    # JSON-safe record (evalsuite and benchmarks read it off
    # `FitResult.to_row()` instead of re-deriving resolution logic).
    result.extras["fit"] = {
        "method": method,
        "impl": cfg.resolved_impl(),
        "precision": cfg.precision,
        "autotune": cfg.autotune,
        "seed": int(cfg.seed),
        "source": type(source).__name__,
    }
    return result


def lower_fit(
    data,
    config: BigMeansConfig | None = None,
    *,
    method: str = "auto",
    key: jax.Array | None = None,
    n_features: int | None = None,
    **overrides,
) -> jax.stages.Lowered:
    """Lower, without running it, the jitted program that :func:`fit` runs
    with the same arguments.

    Its ``compile().as_text()`` names each device operation of a profile of
    that fit by its instruction; :func:`repro.spans.op_scopes` maps the
    names to the ``repro.fit.*`` scopes.  Only strategies that run as one
    jitted call have such a program (``sequential``, ``batched``, and
    ``auto`` where it picks one of them); any other raises ValueError.
    """
    cfg = _resolve_config(config, overrides)
    source = _resolve_source(cfg, data, n_features)
    with _tuning(cfg, source):
        _resolve_method(method)
        if key is None:
            key = jax.random.PRNGKey(cfg.seed)
        program = strategies.plan(method, cfg, source, key)
        if program is None:
            raise ValueError(
                f"method {method!r} does not run as one jitted program here; "
                "lower_fit covers 'sequential' and 'batched'")
        return program.lower()


def evaluate(result_or_centroids, data) -> tuple[jax.Array, float]:
    """Full-data evaluation: ``(assignments [m], objective f(C, X))``.

    The like-for-like comparison across methods whose native ``objective``
    fields have different scopes (chunk, coreset, full data).
    """
    from repro.core.objective import full_assignment

    centroids = getattr(result_or_centroids, "centroids", result_or_centroids)
    X = as_source(data).as_array()
    ids, f = full_assignment(jax.numpy.asarray(X, dtype=jax.numpy.float32),
                             jax.numpy.asarray(centroids))
    return ids, float(f)
