"""The out-of-core accept loop: one host loop for every composition.

Chunks are *fetched* by a provider (memmap slice, distributed-FS shard,
synthetic generator), staged through a prefetch pipeline, and fed to the
jitted ``chunk_step`` / ``chunk_step_batched`` kernels — on one device or,
with a :class:`~repro.engine.topology.StreamMesh`, with the stream batch
axis sharded over a device mesh (out-of-core data on multi-device hardware:
the production big-data scenario).  Capabilities (checkpoint/resume, VNS,
time budget, tracing, fetch-failure skip) come from the middleware stack,
not from the loop body.

Design properties (DESIGN.md §6) are unchanged from the historical runner:

* **fault tolerance** — global state is (C, degenerate, f_best, step, key):
  kilobytes.  A lost/failed chunk is simply skipped: chunks are i.i.d.
  uniform samples, so dropping one changes nothing statistically.  On top
  of that baseline, :mod:`repro.engine.faults` adds bounded retries with
  deterministic backoff (``cfg.retries``), a fetch watchdog that turns a
  hung provider into a retryable fault (``cfg.fetch_timeout_s``), and a
  chunk sanitizer + post-accept invariant guard (``cfg.validate_chunks``)
  that quarantine NaN/Inf/wrong-shape chunks before they can poison
  ``f_best`` acceptance.
* **replay invariance** — per-chunk keys are ``fold_in(key, chunk_id)``:
  restarts, batch sizes, prefetch depths and device counts replay the
  identical sample stream.
* **pipelining** — a background thread prefetches chunks into a bounded
  queue and stages them on device; under ``precision='bf16'`` it casts on
  the host first, halving host→device bytes, and under ``'int8'`` it
  quantizes on the host (per-feature scales) and ships int8 codes — a
  quarter of the f32 link bytes — dequantizing on device off the main
  thread so downstream consumers still see a plain f32 chunk.  Double
  buffering continues *inside* the fused kernel: the ``pipeline='dma'``
  autotune candidate overlaps the HBM copy of point tile i+1 with compute
  on tile i (``kernels/fused_step.py``).

Two stream-state modes share the loop:

* **fold** (collective sync, the historical behaviour): one incumbent;
  each batch broadcasts it into B streams, steps, and argmin-reduces back.
* **persistent streams** (periodic/competitive sync, and the
  ``competitive_s`` scheduler): B incumbents persist across batches and
  exchange only at sync boundaries — the paper's competitive mode, now
  expressible out-of-core.
"""
from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bigmeans
from repro.engine import faults
from repro.engine import middleware as mw
from repro.engine import scheduler as sched_lib
from repro.engine import sync as sync_lib
from repro.engine import topology as topo_lib

ChunkProvider = Callable[[int], np.ndarray]


class EndOfStream(Exception):
    """Raised by a provider to end the run cleanly before ``n_chunks``
    (e.g. a finite chunk iterator ran dry).  Not counted as a failure."""


@dataclasses.dataclass
class RunnerMetrics:
    """``trace`` holds ``(chunk_id, f_best, f_new)`` progress entries plus
    the structured events: ``("fetch_error", chunk_id, "ExcType: message")``
    for failed fetches (retries exhausted), ``("quarantine", chunk_id,
    reason)`` for chunks that arrived with unusable data (NaN/Inf, wrong
    shape), ``("budget_drop", (chunk_ids...))`` for chunks fetched but
    dropped un-stepped at a budget stop, ``("short_chunk", cid, rows,
    need)`` for ragged tails, and ``("ckpt_fallback", step)`` when restore
    healed past a corrupt checkpoint — so ``chunks_done + chunks_failed +
    chunks_dropped + chunks_quarantined`` always reconciles with the number
    of chunks fetched."""
    chunks_done: int = 0
    chunks_failed: int = 0
    chunks_dropped: int = 0
    chunks_quarantined: int = 0
    accepted: int = 0
    lloyd_iters: int = 0
    seed_steps: int = 0
    wall_time_s: float = 0.0
    f_best: float = float("inf")
    trace: list = dataclasses.field(default_factory=list)
    # multi-host runs (repro.engine.hostmesh): the final per-rank health
    # gather — {"rank", "processes", "winner_rank", "per_rank": [...]}.
    host: dict | None = None


class _FetchFailure:
    """A failed chunk fetch: carries the provider's exception type+message,
    its fault class and how many attempts were burned on it."""

    __slots__ = ("error", "kind", "attempts")

    def __init__(self, exc: BaseException, kind: str = faults.TRANSIENT,
                 attempts: int = 1):
        self.error = f"{type(exc).__name__}: {exc}"
        self.kind = kind
        self.attempts = attempts


def _stage_quantized(arr):
    """int8 host->device hand-off for the prefetch pipeline.

    Quantize on the host thread (per-feature scales, the canonical scheme
    of :mod:`repro.kernels.precision`), ship int8 codes + one f32 scale row
    — roughly a quarter of the f32 host->device bytes — then dequantize on
    device, still off the main thread.  The consumer sees a plain f32 chunk
    (sanitizer, K-means++ seeding and stream slicing are untouched) whose
    values are exactly the quantized representation; ``lloyd`` re-quantizes
    deterministically, so results are identical to shipping f32.

    Non-finite chunks ship unquantized: NaN/Inf must reach the chunk
    sanitizer verbatim (int8 codes would silently launder them into
    in-range garbage).
    """
    from repro.kernels import precision as px

    if not np.isfinite(arr).all():
        return jax.device_put(arr)
    q, scale = px.host_quantize(arr)
    qd = jax.device_put(q)
    sd = jax.device_put(scale)
    return qd.astype(jnp.float32) * sd[None, :]


def _fetch_resilient(provider, cid, fault_injector, dtype, *,
                     retry=None, timeout=None, wait=time.sleep,
                     aborted=None, stage=jax.device_put):
    """One guarded chunk fetch: watchdog + classify + bounded retry.

    Returns the device-staged chunk, raises :class:`EndOfStream`, or
    returns a :class:`_FetchFailure` once the fault is terminal (permanent
    class, or a transient one with the retry budget exhausted).  A hung
    provider becomes a retryable :class:`repro.engine.faults.FetchTimeout`
    via the watchdog, so the calling thread is never blocked for longer
    than ``timeout`` per attempt.  ``stage`` is the host->device hand-off
    (:func:`_stage_quantized` under ``precision='int8'``).
    """

    def attempt_once():
        if fault_injector is not None:
            fault_injector(cid)
        return np.asarray(provider(cid), dtype=dtype)

    attempt = 0
    while True:
        try:
            arr = faults.call_with_timeout(
                attempt_once, timeout, name=f"fetch-watchdog-{cid}")
            return stage(arr)
        except EndOfStream:
            raise
        except Exception as exc:
            kind = faults.classify(exc)
            retries = retry.retries if retry is not None else 0
            if (kind == faults.TRANSIENT and attempt < retries
                    and not (aborted is not None and aborted())):
                wait(retry.delay(cid, attempt))
                attempt += 1
                continue
            return _FetchFailure(exc, kind=kind, attempts=attempt + 1)


class _Prefetcher:
    """Background chunk fetcher: provider call + np conversion + device_put
    run off the main thread, double-buffered through a bounded queue.

    Yields ``(chunk_id, chunk-or-_FetchFailure)`` in id order; a
    ``_FetchFailure`` marks a failed fetch (the provider raised, or kept
    raising transiently past the retry budget) so the consumer can account
    for it and record the cause.  With ``timeout`` set, each provider call
    runs under the :func:`repro.engine.faults.call_with_timeout` watchdog:
    a hung provider is abandoned on a daemon thread and surfaces as a
    retryable fault, so the worker — and therefore :meth:`close` — stays
    deterministic.
    """

    _DONE = object()

    def __init__(self, provider, ids, depth,
                 fault_injector=None, dtype=np.float32,
                 retry=None, timeout=None, stage=jax.device_put):
        self._provider = provider
        self._ids = ids
        self._dtype = dtype
        self._fault_injector = fault_injector
        self._retry = retry
        self._timeout = timeout
        self._stage = stage
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, daemon=True)
        self._thread.start()

    def _fetch(self, cid):
        try:
            return _fetch_resilient(
                self._provider, cid, self._fault_injector, self._dtype,
                retry=self._retry, timeout=self._timeout,
                wait=self._stop.wait, aborted=self._stop.is_set,
                stage=self._stage)
        except EndOfStream:
            return self._DONE

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _work(self):
        for cid in self._ids:
            if self._stop.is_set():
                return
            item = self._fetch(cid)
            if item is self._DONE:          # provider signalled end-of-stream
                break
            if not self._put((cid, item)):
                return
        self._put(self._DONE)

    def __iter__(self) -> Iterator:
        while True:
            item = self._q.get()
            if item is self._DONE:
                return
            yield item

    def close(self):
        self._stop.set()
        # Drain so a blocked producer can observe the stop flag and exit.
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=5.0)


def _sync_chunks(provider, ids, fault_injector, dtype=np.float32,
                 retry=None, timeout=None, stage=jax.device_put):
    """prefetch=0 fallback: fetch in the main thread (debug / determinism),
    with the same retry/watchdog semantics as the prefetch pipeline."""
    for cid in ids:
        try:
            yield cid, _fetch_resilient(
                provider, cid, fault_injector, dtype,
                retry=retry, timeout=timeout, stage=stage)
        except EndOfStream:
            return


def _mesh_put(topology, tree):
    """Shard leading (stream) axes over the stream mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(topology.mesh, P(topology.axis))
    return jax.tree.map(lambda a: jax.device_put(a, sharding), tree)


class _StepKernel:
    """One batched accept step against the chosen topology."""

    def __init__(self, cfg, key, topology):
        self.cfg = cfg
        self.key = key
        self.topology = topology
        self._mesh_step = None

    def _kwargs(self):
        cfg = self.cfg
        return dict(max_iters=cfg.max_iters, tol=cfg.tol,
                    candidates=cfg.candidates, impl=cfg.impl,
                    precision=getattr(cfg, "precision", "auto"))

    def keys_for(self, cids):
        # Per-chunk keys are folded from (seed, chunk_id): restarts, batch
        # sizes and worker-count changes replay the identical sample stream.
        return [jax.random.fold_in(self.key, cid) for cid in cids]

    def step_one(self, chunk, state, cid):
        return bigmeans.chunk_step(
            chunk, state, self.keys_for([cid])[0], **self._kwargs())

    def step_states(self, chunks, states, cids):
        """Advance B persistent streams by their chunks (stacked [B, s, n])."""
        keys = jnp.stack(self.keys_for(cids))
        mesh = isinstance(self.topology, topo_lib.StreamMesh)
        if mesh and chunks.shape[0] % self.topology.devices == 0:
            chunks, states, keys = _mesh_put(
                self.topology, (chunks, states, keys))
            return self._sharded_step()(chunks, states, keys)
        return bigmeans.chunk_step_batched(
            chunks, states, keys, **self._kwargs())

    def _sharded_step(self):
        """The batched step with each device running its own streams.

        A ``shard_map`` rather than a sharded ``jit``: XLA cannot partition
        the Pallas kernels inside the step by itself, and the streams are
        independent, so each device simply steps its slice of them.
        """
        if self._mesh_step is None:
            from jax.sharding import PartitionSpec as P

            spec = P(self.topology.axis)
            step = functools.partial(bigmeans.chunk_step_batched,
                                     **self._kwargs())
            self._mesh_step = jax.jit(jax.shard_map(
                step, mesh=self.topology.mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False))
        return self._mesh_step

    def step_fold(self, state, pending):
        """Advance one incumbent by len(pending) concurrent chunk streams."""
        if len(pending) == 1:
            return self.step_one(pending[0][1], state, pending[0][0])
        chunks = jnp.stack([c for _, c in pending])
        states = bigmeans.broadcast_state(state, len(pending))
        states, info = self.step_states(
            chunks, states, [cid for cid, _ in pending])
        return bigmeans.reduce_state(states, base=state), info


def run_stream(
    provider: ChunkProvider,
    cfg,
    *,
    n_features: int,
    resume: bool = True,
    fault_injector: Callable[[int], None] | None = None,
    key: jax.Array | None = None,
    middlewares=None,
    topology=None,
    scheduler=None,
    sync=None,
    host=None,
) -> tuple[bigmeans.BigMeansState, RunnerMetrics]:
    """Stream chunks through Big-means until the chunk count or a middleware
    stop condition (time budget, custom) ends the run.

    ``cfg`` is a `repro.api.BigMeansConfig` (or anything with the same
    fields).  ``middlewares``/``topology``/``scheduler``/``sync`` default to
    the config-derived assembly (:func:`repro.engine.middleware
    .default_stack`, :func:`repro.engine.topology.for_streams`,
    ``cfg.scheduler``, ``cfg.sync``/``cfg.sync_every``).

    ``host`` plugs in a :class:`repro.engine.hostmesh.HostExchanger` for
    multi-host runs: it owns this rank's chunk-id shard, the cross-host
    incumbent exchange at sync windows, and the final argmin-reduce.  With
    ``host=None`` (every single-process run) the loop body is untouched.
    """
    if key is None:
        key = jax.random.PRNGKey(cfg.seed)
    scheduler = scheduler if scheduler is not None else sched_lib.get_scheduler(
        getattr(cfg, "scheduler", "uniform"), cfg)
    sync = sync if sync is not None else sync_lib.from_config(cfg)
    topology = topology if topology is not None else topo_lib.for_streams(cfg)
    if isinstance(topology, topo_lib.WorkerMesh):
        raise ValueError(
            "the stream loop parallelizes over the stream axis; use "
            "StreamMesh (or the 'sharded' strategy for worker meshes)")
    if isinstance(topology, topo_lib.HostMesh) and host is None:
        raise ValueError(
            "host_mesh runs go through repro.engine.hostmesh."
            "run_host_stream (or fit(), which routes there): the stream "
            "loop needs the exchanger's chunk-id shard and sync hooks")
    if middlewares is None:
        stack = mw.default_stack(cfg)
    elif isinstance(middlewares, mw.MiddlewareStack):
        stack = middlewares
    else:
        stack = mw.MiddlewareStack(middlewares)

    competitive_sched = isinstance(scheduler, sched_lib.CompetitiveS)
    persistent = competitive_sched or (cfg.batch > 1 and sync.every != 1)
    if persistent and cfg.vns_ladder:
        raise ValueError(
            "vns_ladder requires collective sync (sync_every=1): the ladder "
            "re-sizes the single incumbent's chunks, which is incompatible "
            "with persistent per-stream incumbents")
    if competitive_sched and isinstance(topology, topo_lib.StreamMesh):
        raise ValueError(
            "competitive_s schedules ragged per-stream chunk sizes, which "
            "cannot shard over a stream mesh; use the single-device "
            "topology")

    state = bigmeans.init_state(cfg.k, n_features)
    metrics = RunnerMetrics()
    ctx = mw.EngineContext(cfg=cfg, key=key, metrics=metrics, state=state,
                           t0=time.monotonic(), last_s=cfg.s)
    ckpt = stack.find(mw.Checkpoint)
    if resume and ckpt is not None:
        ckpt.maybe_restore(ctx, state)
        state, key = ctx.state, ctx.key
    start_chunk = ctx.start_step
    if host is not None:
        # collective start: every rank adopts rank 0's restored
        # (state, key, step) so the fleet resumes the same global window
        state, key, start_chunk = host.sync_start(ctx, state, key)
    metrics.f_best = float(np.asarray(state.f_best).min())

    from repro.kernels import precision as px

    precision = getattr(cfg, "precision", "auto")
    host_dtype = px.host_dtype(precision) or np.float32
    # int8 ships quantized codes over the host->device link (~1/4 of the
    # f32 bytes) and dequantizes on device, still off the main thread.
    stage = _stage_quantized if precision == "int8" else jax.device_put
    ids = (host.chunk_ids(start_chunk) if host is not None
           else range(start_chunk, cfg.n_chunks))
    retry = faults.RetryPolicy.from_config(cfg)
    timeout = getattr(cfg, "fetch_timeout_s", None)
    source = (
        _Prefetcher(provider, ids, cfg.prefetch, fault_injector, host_dtype,
                    retry=retry, timeout=timeout, stage=stage)
        if cfg.prefetch > 0
        else _sync_chunks(provider, ids, fault_injector, host_dtype,
                          retry=retry, timeout=timeout, stage=stage)
    )
    kernel = _StepKernel(cfg, key, topology)
    ctx.extras["stream_mode"] = "persistent" if persistent else "fold"
    stack.on_start(ctx)

    runner_fn = _run_persistent if persistent else _run_fold
    try:
        state = runner_fn(source, state, ctx, stack, kernel, scheduler, sync,
                          host)
    finally:
        if isinstance(source, _Prefetcher):
            source.close()

    ctx.state = state
    ctx.step = start_chunk + metrics.chunks_done
    if host is not None:
        # final cross-host argmin-reduce + counter merge + health gather;
        # a dead peer surfaces here as a typed HostDead, never a hang
        state = host.finalize(ctx, state)
        ctx.state = state
        ctx.step = host.global_step
    stack.on_finish(ctx)
    metrics.wall_time_s = time.monotonic() - ctx.t0
    metrics.f_best = float(np.asarray(state.f_best).min())
    return state, metrics


def _drop_pending(ctx, pending):
    """Budget-stop accounting for fetched-but-unstepped chunks (so
    done + failed + dropped + quarantined reconciles with fetched)."""
    if pending:
        ctx.metrics.chunks_dropped += len(pending)
        ctx.metrics.trace.append(
            ("budget_drop", tuple(cid for cid, _ in pending)))


def _sanitize(ctx, stack, chunk_id, chunk):
    """Run the middleware transform chain; a quarantined chunk is counted
    and traced, and ``None`` is returned so the loop skips it."""
    try:
        return stack.transform_chunk(ctx, chunk_id, chunk)
    except faults.ChunkQuarantined as q:
        ctx.metrics.chunks_quarantined += 1
        ctx.metrics.trace.append(("quarantine", chunk_id, q.reason))
        return None


def _consume_info(ctx, info):
    m = ctx.metrics
    m.accepted += int(np.sum(np.asarray(info.accepted)))
    m.lloyd_iters += int(np.sum(np.asarray(info.lloyd_iters)))
    m.seed_steps += int(np.sum(np.asarray(info.seed_steps)))


def _run_fold(source, state, ctx, stack, kernel, scheduler, sync, host=None):
    """Collective mode: one incumbent, argmin-reduced after every batch."""
    cfg = ctx.cfg
    metrics = ctx.metrics
    pending: list = []

    def flush(state):
        state, info = kernel.step_fold(state, pending)
        metrics.chunks_done += len(pending)
        ctx.last_cid = pending[-1][0]
        pending.clear()
        _consume_info(ctx, info)
        if host is not None:
            # cross-host exchange BEFORE after_window, so the (rank-0)
            # checkpoint holds the post-exchange global incumbent at the
            # global chunk frontier
            state = host.fold_boundary(ctx, state)
        ctx.state, ctx.info = state, info
        ctx.step = (host.global_step if host is not None
                    else ctx.start_step + metrics.chunks_done)
        stack.after_window(ctx)
        return state

    stopped = False
    for chunk_id, chunk in source:
        if stack.should_stop(ctx):
            stopped = True
            # the item in hand was already consumed from the source:
            # account for it (failed or dropped), never lose it silently
            if isinstance(chunk, _FetchFailure):
                stack.on_fetch_error(ctx, chunk_id, chunk.error)
            elif chunk is None:
                metrics.chunks_failed += 1
            else:
                pending.append((chunk_id, chunk))
            break
        if chunk is None or isinstance(chunk, _FetchFailure):
            if isinstance(chunk, _FetchFailure):
                stack.on_fetch_error(ctx, chunk_id, chunk.error)
            else:
                metrics.chunks_failed += 1
            continue
        chunk = _sanitize(ctx, stack, chunk_id, chunk)
        if chunk is None:
            continue
        if pending and chunk.shape != pending[0][1].shape:
            # ragged chunk (short tail / VNS rung change mid-batch):
            # flush the homogeneous batch first, then start a new one
            state = flush(state)
        if chunk.shape[0] != ctx.last_s and np.isfinite(float(state.f_best)):
            # objectives are sums over s points: rescale the incumbent's
            # objective so acceptance compares per-point quality
            state = state._replace(
                f_best=state.f_best * (chunk.shape[0] / ctx.last_s))
        ctx.last_s = chunk.shape[0]
        pending.append((chunk_id, chunk))
        if len(pending) < cfg.batch:
            continue
        state = flush(state)
        if stack.should_stop(ctx):
            stopped = True
            break
    else:
        if pending:                     # final partial batch
            state = flush(state)
    if stopped:
        _drop_pending(ctx, pending)
    return state


def _run_persistent(source, state, ctx, stack, kernel, scheduler, sync,
                    host=None):
    """Persistent-stream mode: B incumbents advance across batches and
    exchange only at sync boundaries (periodic/competitive modes, and the
    ``competitive_s`` sample-size race)."""
    cfg = ctx.cfg
    metrics = ctx.metrics
    B = cfg.batch
    base = state                        # restored counters live here
    states = bigmeans.broadcast_state(state, B)
    sizes = list(scheduler.sizes(B))
    if any(s is None for s in sizes):
        sizes = [cfg.s] * B
    round_idx = 0
    pending: list = []
    competitive_sched = isinstance(scheduler, sched_lib.CompetitiveS)
    eval_chunk = None                   # last full-size chunk (common eval)

    def stream_scores(states):
        """Every incumbent scored on the SAME evaluation chunk — chunk
        objectives at different sizes are not comparable (small chunks
        overfit), a shared eval set is."""
        from repro.core.objective import chunk_objective

        return np.asarray(jax.vmap(
            lambda c: chunk_objective(eval_chunk, c, impl=cfg.impl)
        )(states.centroids), dtype=np.float64)

    def stream_slices(pending):
        """Assign this round's chunks to streams 0..len(pending)-1 and
        group them by that stream's chunk size.  A chunk too short for its
        stream (ragged tail of a finite source) is skipped — chunks are
        i.i.d. samples, so dropping one is statistically free — and
        returned for accounting."""
        groups: dict[int, list] = {}
        skipped: list = []
        for b, (cid, chunk) in enumerate(pending):
            s_b = sizes[b]
            if chunk.shape[0] < s_b:
                skipped.append((cid, int(chunk.shape[0]), s_b))
                continue
            groups.setdefault(s_b, []).append((b, cid, chunk[:s_b]))
        return groups, skipped

    def step_round(states, pending):
        groups, skipped = stream_slices(pending)
        for cid, rows, need in skipped:
            metrics.chunks_dropped += 1
            metrics.trace.append(("short_chunk", cid, rows, need))
        for s_b, members in sorted(groups.items()):
            idx = np.asarray([b for b, _, _ in members])
            chunks = jnp.stack([c for _, _, c in members])
            sub = jax.tree.map(lambda a: a[idx], states)
            sub, info = kernel.step_states(
                chunks, sub, [cid for _, cid, _ in members])
            states = jax.tree.map(
                lambda a, u: a.at[idx].set(u), states, sub)
            _consume_info(ctx, info)
            ctx.info = info
        metrics.chunks_done += len(pending) - len(skipped)
        ctx.last_cid = pending[-1][0]
        return states

    def reduce(states):
        """Final keep-the-best across streams.  At uniform sizes this is
        the plain argmin; under competitive_s the incumbents are scored on
        the common eval chunk (raw objectives are size-incomparable)."""
        if competitive_sched and eval_chunk is not None:
            w = int(np.argmin(stream_scores(states)))
        else:
            f = np.asarray(states.f_best, dtype=np.float64)
            w = int(np.argmin(f / np.asarray(sizes, dtype=np.float64)))
        ctx.extras["winner_s"] = int(sizes[w])
        return bigmeans.BigMeansState(
            centroids=states.centroids[w],
            degenerate=states.degenerate[w],
            f_best=states.f_best[w],
            n_accepted=jnp.sum(states.n_accepted) + base.n_accepted,
            n_dist_evals=jnp.sum(states.n_dist_evals) + base.n_dist_evals,
        )

    def boundary(states):
        nonlocal sizes
        if (round_idx + 1) % cfg.sync_every == 0:
            # scheduler observation window: competitive_s scores every
            # incumbent on the shared eval chunk and reallocates here
            if competitive_sched and eval_chunk is not None:
                scores = [float(f) for f in stream_scores(states)]
            else:
                scores = [float(f) for f in np.asarray(states.f_best)]
            moves = scheduler.observe_window(scores, list(sizes))
            for b, new_s, clone_from in moves:
                ratio = new_s / sizes[clone_from]
                states = states._replace(
                    centroids=states.centroids.at[b].set(
                        states.centroids[clone_from]),
                    degenerate=states.degenerate.at[b].set(
                        states.degenerate[clone_from]),
                    f_best=states.f_best.at[b].set(
                        states.f_best[clone_from] * ratio),
                )
            sizes = list(scheduler.sizes(B))
        if sync.boundary(round_idx):
            if competitive_sched and eval_chunk is not None:
                # cross-size collective exchange: every stream continues
                # from the eval winner, acceptance threshold rescaled to
                # its own chunk size (same per-point quality)
                scores = stream_scores(states)
                w = int(np.argmin(scores))
                s_eval = eval_chunk.shape[0]
                ratios = jnp.asarray(
                    [s_b / s_eval for s_b in sizes], dtype=jnp.float32)
                states = states._replace(
                    centroids=jnp.broadcast_to(
                        states.centroids[w], states.centroids.shape),
                    degenerate=jnp.broadcast_to(
                        states.degenerate[w], states.degenerate.shape),
                    f_best=jnp.float32(scores[w]) * ratios,
                )
            elif len(set(sizes)) == 1:
                # periodic argmin exchange (comparable only at equal sizes)
                states = bigmeans._sync_streams(states)
        return states

    stopped = False
    for chunk_id, chunk in source:
        if stack.should_stop(ctx):
            stopped = True
            # account for the consumed-but-unstepped item in hand
            if isinstance(chunk, _FetchFailure):
                stack.on_fetch_error(ctx, chunk_id, chunk.error)
            elif chunk is None:
                metrics.chunks_failed += 1
            else:
                pending.append((chunk_id, chunk))
            break
        if chunk is None or isinstance(chunk, _FetchFailure):
            if isinstance(chunk, _FetchFailure):
                stack.on_fetch_error(ctx, chunk_id, chunk.error)
            else:
                metrics.chunks_failed += 1
            continue
        chunk = _sanitize(ctx, stack, chunk_id, chunk)
        if chunk is None:               # quarantined: never the eval set
            continue
        eval_chunk = chunk              # raw (unsliced): the common eval set
        pending.append((chunk_id, chunk))
        if len(pending) < B:
            continue
        states = step_round(states, pending)
        pending = []
        if host is None:
            ctx.state = reduce(states)
            ctx.step = ctx.start_step + metrics.chunks_done
            stack.after_window(ctx)
            states = boundary(states)
        else:
            # host order: local boundary (observe + local sync) first, then
            # the cross-host exchange, and only then checkpoint — so the
            # rank-0 checkpoint holds the post-exchange global state at the
            # global window frontier
            states = boundary(states)
            states = host.persistent_boundary(ctx, states, sizes)
            ctx.state = reduce(states)
            ctx.step = host.global_step
            stack.after_window(ctx)
        round_idx += 1
        if stack.should_stop(ctx):
            stopped = True
            break
    else:
        if pending:                     # final partial round
            states = step_round(states, pending)
            pending = []
            ctx.state = reduce(states)
            ctx.step = (host.global_step if host is not None
                        else ctx.start_step + metrics.chunks_done)
            stack.after_window(ctx)
    if stopped:
        _drop_pending(ctx, pending)
    return reduce(states)
