"""Multi-model tenancy: several centroid sets resident and servable at once.

A :class:`ModelRegistry` maps model ids to :class:`ModelEntry` objects.
Each entry owns

* an immutable :class:`CentroidSnapshot` behind an atomic pointer — the
  unit of hot-swap.  A batch launch reads the pointer exactly once, so a
  swap lands between launches and old/new centroids are never mixed within
  one response;
* its own kernel policy (``impl`` resolved once at registration,
  ``precision`` routed through ``kernels/ops.assign`` — the autotuned,
  demotion-aware dispatch, not a hardcoded reference path);
* one jitted assign callable whose Python body doubles as a *recompile
  counter*: the body only executes when jax traces a new shape, so after
  bucket warmup the counter must stay flat (asserted by tests and the
  latency benchmark).

Swaps append a ``("swap", model_id, step)`` event to the registry trace,
the serving twin of the engine's trace-event vocabulary.
"""
from __future__ import annotations

import contextvars
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro import spans
from repro.kernels import ops
from repro.kernels import precision as px

# The batcher's id of the launch running on this thread: the ``launch`` arg
# of the ``repro.serve.dispatch`` / ``fetch`` spans of :meth:`ModelEntry.launch`.
LAUNCH_ID: contextvars.ContextVar[int] = contextvars.ContextVar(
    "repro_serve_launch", default=-1)


@dataclass(frozen=True)
class CentroidSnapshot:
    """One immutable, device-resident centroid set.

    ``version`` increments on every swap; ``step`` is the checkpoint step
    the snapshot came from (None for directly registered arrays).  Every
    :class:`repro.serve.AssignResponse` records the (version, step) that
    served it, so clients and tests can attribute results to exactly one
    centroid generation.  ``t_swapped`` (monotonic seconds) is when this
    generation went live — ``Server.health()`` reports its age.
    """

    centroids: Any          # [k, n] jax array
    version: int
    step: int | None
    t_swapped: float = field(default_factory=time.monotonic, compare=False)

    @property
    def k(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_features(self) -> int:
        return self.centroids.shape[1]


def _as_centroids(obj) -> jax.Array:
    """Accept a raw [k, n] array or anything with a ``.centroids`` field
    (e.g. a :class:`repro.api.FitResult`)."""
    arr = getattr(obj, "centroids", obj)
    arr = jax.numpy.asarray(arr, dtype=jax.numpy.float32)
    if arr.ndim != 2:
        raise ValueError(
            f"centroids must be [k, n], got shape {arr.shape}")
    if not bool(jax.numpy.isfinite(arr).all()):
        raise ValueError("centroids contain non-finite values")
    return arr


class ModelEntry:
    """One resident model: a swappable snapshot + its compiled assign."""

    def __init__(self, model_id: str, centroids, *, impl: str = "auto",
                 precision: str = "auto", donate: bool = False):
        arr = _as_centroids(centroids)
        self.model_id = model_id
        self.impl = ops.resolve_impl(impl)
        self.precision = px.resolve(precision, arr.dtype)
        self._lock = threading.Lock()
        self._snapshot = CentroidSnapshot(arr, version=0, step=None)
        self._recompiles = 0
        self._donate = donate
        self._assign = self._build_assign()
        self._fallback_assign = None             # built lazily / at warmup
        self._demoted_buckets: set[int] = set()

    # -- kernel dispatch ----------------------------------------------------
    def _build_assign(self):
        def _assign(q, c):
            # Executes only while jax traces a new (bucket, k, n) shape —
            # a free, exact recompile counter for the serving hot path.
            self._recompiles += 1
            return ops.assign(q, c, impl=self.impl, precision=self.precision)

        donate = (0,) if self._donate else ()
        return jax.jit(_assign, donate_argnums=donate)

    def _fallback(self):
        # Ref-path launch for transient-fault retries and demoted buckets.
        # Its own jit (never donated: a retry must be able to rebuild the
        # buffer), its own trace counter — warming it never perturbs the
        # primary zero-recompile contract.
        with self._lock:
            if self._fallback_assign is None:
                self._fallback_assign = jax.jit(
                    lambda q, c: ops.assign(
                        q, c, impl="ref", precision=self.precision))
            return self._fallback_assign

    def launch(self, q, snapshot: CentroidSnapshot
               ) -> tuple[np.ndarray, np.ndarray]:
        """Run one coalesced assignment launch against ``snapshot``.

        The batcher calls this with the padded request buffer (host or
        device); it is a method (not an inlined jit call) so tests can wrap
        it to simulate slow kernels without touching the queueing logic.  A
        bucket the batcher demoted (repeated primary failures) routes
        straight to the ref fallback.
        """
        if int(q.shape[0]) in self._demoted_buckets:
            return self.launch_fallback(q, snapshot)
        return self._run(self._assign, q, snapshot)

    def launch_fallback(self, q, snapshot: CentroidSnapshot
                        ) -> tuple[np.ndarray, np.ndarray]:
        """The ref-path launch: where transient launch faults retry."""
        return self._run(self._fallback(), q, snapshot)

    @staticmethod
    def _run(assign, q, snapshot: CentroidSnapshot
             ) -> tuple[np.ndarray, np.ndarray]:
        launch = LAUNCH_ID.get()
        with TraceAnnotation(spans.SERVE_DISPATCH, launch=launch):
            ids, d = assign(jax.numpy.asarray(q), snapshot.centroids)
        with TraceAnnotation(spans.SERVE_FETCH, launch=launch):
            return np.asarray(ids), np.asarray(d)

    def demote_bucket(self, bucket: int, exc: Exception) -> None:
        """Pin ``bucket`` to the ref path for this entry's lifetime, and
        record the failure in the process-wide kernel demotion table (so
        eager dispatches at this shape skip the Pallas path too)."""
        self._demoted_buckets.add(int(bucket))
        if self.impl in ("pallas", "pallas_interpret"):
            snap = self.snapshot()
            ops.record_demotion(
                "assign", self.impl, (1, int(bucket), snap.k, snap.n_features),
                self.precision, exc)

    def is_demoted(self, bucket: int) -> bool:
        return int(bucket) in self._demoted_buckets

    @property
    def demoted_buckets(self) -> tuple[int, ...]:
        return tuple(sorted(self._demoted_buckets))

    def warmup(self, buckets: tuple[int, ...]) -> None:
        """Pre-pay every per-bucket cost off the request path.

        For each padded shape bucket this (1) runs the *eager*
        demotion-aware dispatch via :func:`repro.kernels.ops.warm_assign`,
        so the autotune cache is consulted/populated and a failing Pallas
        build demotes this exact serving shape to the ref path now — the
        same way ``fit()`` pre-tunes ``fused_step`` — and (2) compiles the
        jitted serving call, so traffic never waits on a trace.
        """
        snap = self.snapshot()
        n = snap.n_features
        for b in buckets:
            ops.warm_assign(b, snap.k, n, impl=self.impl,
                            precision=self.precision)
            q = jax.numpy.zeros((b, n), jax.numpy.float32)
            jax.block_until_ready(self._assign(q, snap.centroids))
            # Compile the ref fallback too: a transient launch fault must
            # retry immediately, not pay a trace on the request path.
            jax.block_until_ready(self._fallback()(q, snap.centroids))

    # -- snapshot management ------------------------------------------------
    def snapshot(self) -> CentroidSnapshot:
        """The current centroid generation (atomic read)."""
        with self._lock:
            return self._snapshot

    def swap(self, centroids, *, step: int | None = None) -> CentroidSnapshot:
        """Atomically replace the serving centroids.

        The new set must match the resident (k, n) — same shape means the
        compiled per-bucket executables are reused as-is, so a swap costs
        one pointer write and zero recompiles, and in-flight requests are
        neither dropped nor re-queued: launches already in progress finish
        on the old snapshot, the next launch reads the new one.
        """
        arr = _as_centroids(centroids)
        with self._lock:
            old = self._snapshot
            if arr.shape != old.centroids.shape:
                raise ValueError(
                    f"swap shape mismatch for {self.model_id!r}: resident "
                    f"{tuple(old.centroids.shape)}, new {tuple(arr.shape)}")
            new = CentroidSnapshot(arr, version=old.version + 1, step=step)
            self._snapshot = new
        return new

    @property
    def recompiles(self) -> int:
        """How many times the serving assign has been traced (one per
        warmed bucket; must not grow under steady traffic)."""
        return self._recompiles


class ModelRegistry:
    """Thread-safe id -> :class:`ModelEntry` map with a swap trace."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[str, ModelEntry] = {}
        self.trace: list = []

    def register(self, model_id: str, centroids, *, impl: str = "auto",
                 precision: str = "auto", donate: bool = False) -> ModelEntry:
        entry = ModelEntry(model_id, centroids, impl=impl,
                           precision=precision, donate=donate)
        with self._lock:
            if model_id in self._entries:
                raise ValueError(
                    f"model {model_id!r} already registered; use swap() to "
                    "replace its centroids")
            self._entries[model_id] = entry
        return entry

    def get(self, model_id: str) -> ModelEntry:
        with self._lock:
            try:
                return self._entries[model_id]
            except KeyError:
                raise KeyError(
                    f"unknown model {model_id!r}; registered: "
                    f"{sorted(self._entries)}") from None

    def unregister(self, model_id: str) -> None:
        with self._lock:
            self._entries.pop(model_id, None)

    def list_models(self) -> list[str]:
        with self._lock:
            return sorted(self._entries)

    def record(self, event: tuple) -> None:
        """Append a structured serving event to the trace (thread-safe).
        The batcher and circuit breaker route their ``launch_fault`` /
        ``deadline_shed`` / ``breaker_*`` / ``worker_restart`` events here."""
        with self._lock:
            self.trace.append(event)

    def swap(self, model_id: str, centroids, *,
             step: int | None = None) -> CentroidSnapshot:
        """Hot-swap ``model_id``'s centroids; logs ``("swap", id, step)``."""
        snap = self.get(model_id).swap(centroids, step=step)
        self.record(("swap", model_id, step))
        return snap
