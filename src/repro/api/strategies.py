"""Driver strategy registry: how a Big-means fit executes.

Every strategy is an *engine configuration* — an assembly of the
scheduler / topology / sync-policy / middleware pieces from
:mod:`repro.engine` — behind the common ``fit(config, source, key) ->
FitResult`` contract:

* ``sequential`` — the paper's Algorithm 3: single device, scalar stream
  (``engine.incore.sequential``).
* ``batched``    — B incumbent streams per device
  (``engine.incore.batched_local``; with ``topology='stream_mesh'`` the
  stream axis is sharded, ``batched_stream_mesh``).
* ``sharded``    — multi-worker chunk streams with periodic incumbent
  exchange (``engine.incore.worker_sharded``); with checkpointing or a time
  budget the same windows run host-orchestrated
  (``worker_sharded_rounds``) so the middleware stack composes.
* ``streaming``  — the out-of-core host loop (``engine.stream.run_stream``):
  prefetch pipeline, checkpoints, time budget, VNS ladder — on one device,
  with the stream axis sharded (``topology='stream_mesh'``), or scaled out
  over processes (``topology='host_mesh'`` →
  ``engine.hostmesh.run_host_stream``).
* ``auto``       — picks one of the above from the config + data source +
  hardware topology.

Placement is declarative: strategies consume ``cfg.topology`` (a
:class:`repro.engine.topology.TopologySpec`) through
``engine.topology.from_config`` and never hand-build meshes; the deprecated
raw ``cfg.mesh`` rides the same path via the shim, bit-identically.

Strategies are registered by name so follow-up work (competitive sample-size
optimization, stream fusion — arXiv:2403.18766 / 2410.14548) plugs in as
engine configurations instead of new entry points (``competitive_s`` is the
first: set ``config.scheduler='competitive_s'`` on the streaming strategy).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro import spans
from repro.api.config import BigMeansConfig
from repro.api.result import FitResult
from repro.api.sources import DataSource

StrategyFn = Callable[[BigMeansConfig, DataSource, jax.Array], FitResult]


class Program(NamedTuple):
    """A fit that runs as one jitted call: the call, and how its
    ``(state, infos)`` become a :class:`FitResult`."""

    fn: Callable            # a ``jax.jit`` function
    args: tuple
    kwargs: dict
    collect: Callable       # (state, infos) -> FitResult

    def dispatch_args(self) -> dict:
        """How the program runs, as args of the ``repro.fit.dispatch``
        span: ``gather`` and ``g``, the points a gathered row holds, and
        ``lloyd``, ``fused`` or ``two_pass``, with ``k_tiles``, the lane
        tiles of k the fused kernel walks (0 on the two-pass path)."""
        from repro.core.bigmeans import LANES, packed_width
        from repro.kernels import ops
        from repro.kernels import precision as px

        kw = self.kwargs
        gather = kw["gather"]
        X = self.args[0]
        n = X.shape[1]
        g = LANES // packed_width(n) if gather == "packed" else 1
        devices = 1 if kw.get("mesh") is None \
            else kw["mesh"].shape[kw["stream_axis"]]
        batch = kw.get("batch", 1) // devices
        lloyd, k_tiles = ops.lloyd_path(
            "fused_batched" if "batch" in kw else "fused", impl=kw["impl"],
            b=batch, m=kw["s"], k=kw["k"], n=n,
            precision=px.resolve(kw["precision"], X.dtype))
        return {"gather": gather, "g": g, "lloyd": lloyd, "k_tiles": k_tiles}

    def dispatch(self):
        return self.fn(*self.args, **self.kwargs)

    def lower(self) -> jax.stages.Lowered:
        return self.fn.lower(*self.args, **self.kwargs)


PlanFn = Callable[[BigMeansConfig, DataSource, jax.Array], Program]

_STRATEGIES: dict[str, StrategyFn] = {}
_PLANS: dict[str, PlanFn] = {}


def register_strategy(name: str):
    """Decorator: register ``fn(config, source, key) -> FitResult``."""
    def deco(fn: StrategyFn) -> StrategyFn:
        _STRATEGIES[name] = fn
        return fn
    return deco


def register_program(name: str):
    """Decorator: register ``plan(config, source, key) -> Program`` for a
    strategy that runs as one jitted call; the strategy ``name`` dispatches
    the planned program and collects its result."""
    def deco(plan_fn: PlanFn) -> PlanFn:
        def run(cfg, source, key):
            program = plan_fn(cfg, source, key)
            return collect(program, program.dispatch())

        _PLANS[name] = plan_fn
        _STRATEGIES[name] = run
        return plan_fn
    return deco


def plan(method: str, cfg: BigMeansConfig, source: DataSource,
         key: jax.Array) -> Program | None:
    """The one jitted program that ``method`` (``auto`` resolved) runs for
    this fit, or None where it runs several or is a baseline."""
    name = resolve_auto(cfg, source) if method == "auto" else method
    plan_fn = _PLANS.get(name)
    return None if plan_fn is None else plan_fn(cfg, source, key)


def collect(program: Program, out) -> FitResult:
    """Read a dispatched program's output ``(state, infos)`` to the host."""
    with TraceAnnotation(spans.FIT_COLLECT):
        result = program.collect(*out)
        jax.block_until_ready(result.centroids)
    return result


def get_strategy(name: str) -> StrategyFn:
    if name == "auto":
        return _fit_auto
    try:
        return _STRATEGIES[name]
    except KeyError:
        raise KeyError(
            f"unknown strategy {name!r}; known: "
            f"{['auto'] + list_strategies()}") from None


def list_strategies() -> list[str]:
    return sorted(_STRATEGIES)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _require_array(source: DataSource, strategy: str):
    if not source.in_core:
        raise TypeError(
            f"strategy {strategy!r} needs in-core data but the source "
            f"({type(source).__name__}) cannot be materialized; use "
            "strategy='streaming' (or 'auto')")
    return source.as_array()


def _trace_from_infos(infos) -> list:
    f_new = np.asarray(infos.f_new, dtype=np.float64)
    accepted = np.asarray(infos.accepted)
    return [(int(i), float(f), bool(a))
            for i, (f, a) in enumerate(zip(f_new, accepted))]


def _result_from_state(state, infos, cfg, strategy, **extras) -> FitResult:
    seed_steps = infos.seed_steps
    return FitResult(
        centroids=state.centroids,
        objective=float(state.f_best),
        algorithm="big_means",
        strategy=strategy,
        n_chunks=int(np.asarray(infos.f_new).size),
        n_accepted=int(state.n_accepted),
        n_iterations=int(np.sum(np.asarray(infos.lloyd_iters))),
        seed_steps=0 if seed_steps is None
        else int(np.sum(np.asarray(seed_steps))),
        n_dist_evals=float(state.n_dist_evals),
        trace=_trace_from_infos(infos),
        checkpoint_dir=None,
        config=cfg,
        extras=extras,
    )


def _resolve_sync_every(cfg: BigMeansConfig, rounds: int) -> int:
    """Concrete exchange period from the sync-policy knob (``'competitive'``
    resolves to a single final exchange)."""
    from repro.engine import sync as sync_lib

    return sync_lib.from_config(cfg).resolve(rounds)


def _largest_divisor_le(n: int, cap: int) -> int:
    for d in range(min(cap, n), 0, -1):
        if n % d == 0:
            return d
    return 1


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------


@register_program("sequential")
def _plan_sequential(cfg: BigMeansConfig, source: DataSource,
                     key: jax.Array) -> Program:
    from repro.engine import incore

    X = _require_array(source, "sequential")
    return Program(
        incore.sequential, (X, key),
        dict(k=cfg.k, s=cfg.s, n_chunks=cfg.n_chunks,
             max_iters=cfg.max_iters, tol=cfg.tol, candidates=cfg.candidates,
             impl=cfg.impl, with_replacement=cfg.with_replacement,
             precision=cfg.precision,
             gather=incore.gather_for(X, cfg.precision, cfg.s)),
        lambda state, infos: _result_from_state(
            state, infos, cfg, "sequential"))


@register_program("batched")
def _plan_batched(cfg: BigMeansConfig, source: DataSource,
                  key: jax.Array) -> Program:
    from repro.engine import incore
    from repro.engine import topology as topo_lib

    if cfg.n_chunks % cfg.batch:
        raise ValueError(
            f"strategy 'batched' needs batch ({cfg.batch}) to divide "
            f"n_chunks ({cfg.n_chunks})")
    rounds = cfg.n_chunks // cfg.batch
    sync_every = _resolve_sync_every(cfg, rounds)
    if rounds % sync_every:
        raise ValueError(
            f"strategy 'batched' needs sync_every ({sync_every}) to "
            f"divide the round count ({rounds} = n_chunks / batch)")
    topo = topo_lib.for_streams(cfg)
    if not isinstance(topo, (topo_lib.SingleDevice, topo_lib.StreamMesh)):
        raise ValueError(
            f"strategy 'batched' runs on 'single' or 'stream_mesh' "
            f"topologies, got {topo.name!r}")
    mesh = topo.mesh if isinstance(topo, topo_lib.StreamMesh) else None
    stream_axis = topo.axis if mesh is not None else cfg.stream_axis
    if mesh is not None and cfg.batch % topo.devices:
        raise ValueError(
            f"stream mesh has {topo.devices} devices, which must "
            f"divide batch ({cfg.batch})")

    X = _require_array(source, "batched")
    devices = 1 if mesh is None else topo.devices
    kwargs = dict(
        k=cfg.k, s=cfg.s, batch=cfg.batch, rounds=rounds,
        sync_every=sync_every, max_iters=cfg.max_iters, tol=cfg.tol,
        candidates=cfg.candidates, impl=cfg.impl,
        with_replacement=cfg.with_replacement, precision=cfg.precision,
        gather=incore.gather_for(X, cfg.precision,
                                 cfg.batch // devices * cfg.s))
    if mesh is None:
        fn = incore.batched_local
    else:
        fn = incore.batched_stream_mesh
        kwargs.update(mesh=mesh, stream_axis=stream_axis)
    return Program(
        fn, (X, key), kwargs,
        lambda state, infos: _result_from_state(
            state, infos, cfg, "batched", batch=cfg.batch, rounds=rounds))


@register_strategy("sharded")
def _fit_sharded(cfg: BigMeansConfig, source: DataSource,
                 key: jax.Array) -> FitResult:
    from repro.engine import incore, middleware as mw
    from repro.engine import topology as topo_lib

    spec = cfg.topology
    if cfg.mesh is None and spec.kind == "auto" \
            and tuple(cfg.mesh_axes[:1]) != ("data",):
        # legacy axis-name knob without a mesh: honour it through the spec
        spec = topo_lib.TopologySpec(kind="worker_mesh",
                                     axes=tuple(cfg.mesh_axes[:1]))
        topo = topo_lib.resolve(spec, role="worker")
    else:
        topo = topo_lib.for_workers(cfg)
    mesh, workers = topo.mesh, topo.devices
    if cfg.n_chunks % workers:
        raise ValueError(
            f"strategy 'sharded' needs the worker count ({workers}) to "
            f"divide n_chunks ({cfg.n_chunks})")
    chunks_per_worker = cfg.n_chunks // workers
    sync_every = _resolve_sync_every(cfg, chunks_per_worker)
    if chunks_per_worker % sync_every:
        raise ValueError(
            f"strategy 'sharded' needs sync_every ({sync_every}) to "
            f"divide chunks_per_worker ({chunks_per_worker} = "
            f"n_chunks / workers)")

    X = _require_array(source, "sharded")
    kwargs = dict(
        mesh=mesh, k=cfg.k, s=cfg.s, chunks_per_worker=chunks_per_worker,
        sync_every=sync_every, axes=topo.axes,
        max_iters=cfg.max_iters, tol=cfg.tol, candidates=cfg.candidates,
        impl=cfg.impl, with_replacement=cfg.with_replacement,
        precision=cfg.precision,
        gather=incore.gather_for(X, cfg.precision, cfg.s, shards=workers))
    extras = dict(workers=workers, chunks_per_worker=chunks_per_worker)
    if cfg.ckpt_dir is not None or cfg.time_budget_s is not None:
        # middleware composition (checkpoint/resume, time budget): run the
        # same sync windows host-orchestrated, one jitted segment per window
        mws: list = []
        if cfg.ckpt_dir:
            mws.append(mw.Checkpoint(cfg.ckpt_dir, cfg.ckpt_every,
                                     sync_every, step_from="step"))
        if cfg.time_budget_s is not None:
            mws.append(mw.TimeBudget(cfg.time_budget_s))
        state, infos, ctx = incore.worker_sharded_rounds(
            X, key, cfg=cfg, middlewares=mws, resume=cfg.resume, **kwargs)
        result = _result_from_state(
            state, infos, cfg, "sharded",
            rounds_done=ctx.step, **extras)
        result.checkpoint_dir = cfg.ckpt_dir
        return result
    state, infos = incore.worker_sharded(X, key, **kwargs)
    return _result_from_state(state, infos, cfg, "sharded", **extras)


@register_strategy("streaming")
def _fit_streaming(cfg: BigMeansConfig, source: DataSource,
                   key: jax.Array) -> FitResult:
    from repro.engine import hostmesh
    from repro.engine import scheduler as sched_lib
    from repro.engine import stream as engine_stream
    from repro.engine import topology as topo_lib
    from repro.kernels import precision as px

    topology = topo_lib.for_streams(cfg)
    scheduler = sched_lib.get_scheduler(cfg.scheduler, cfg)
    fetch_s = getattr(scheduler, "fetch_s", cfg.s) or cfg.s
    # bf16 precision: chunks are cast on the host (prefetch thread) so
    # host->device transfers move half the bytes, not just HBM reads.
    # host_dtype is None otherwise: the source serves its native default.
    provider = source.provider(
        fetch_s, seed=cfg.seed, with_replacement=cfg.with_replacement,
        dtype=px.host_dtype(cfg.precision))
    if isinstance(topology, topo_lib.HostMesh):
        # multi-host scale-out: this process runs its chunk-id shard and
        # exchanges incumbents at sync windows (run_host_stream builds the
        # rank-local scheduler, so the config-level one is discarded)
        state, metrics = hostmesh.run_host_stream(
            provider, cfg, topology=topology, n_features=source.n_features,
            resume=cfg.resume, key=key)
    else:
        state, metrics = engine_stream.run_stream(
            provider, cfg, n_features=source.n_features, resume=cfg.resume,
            key=key, scheduler=scheduler, topology=topology)
    extras = {"chunks_failed": metrics.chunks_failed,
              "chunks_dropped": metrics.chunks_dropped,
              "chunks_quarantined": metrics.chunks_quarantined}
    # Run-health summary: the reconciliation contract in one record —
    # done + failed + dropped + quarantined == chunks fetched.
    extras["health"] = {
        "chunks_done": metrics.chunks_done,
        "chunks_failed": metrics.chunks_failed,
        "chunks_dropped": metrics.chunks_dropped,
        "chunks_quarantined": metrics.chunks_quarantined,
        "chunks_fetched": (metrics.chunks_done + metrics.chunks_failed
                           + metrics.chunks_dropped
                           + metrics.chunks_quarantined),
        "ckpt_fallback": next(
            (t[1] for t in metrics.trace if t[0] == "ckpt_fallback"), None),
        "quarantine_reasons": [
            (t[1], t[2]) for t in metrics.trace if t[0] == "quarantine"],
    }
    if metrics.host is not None:
        # the final cross-host gather: every rank's reconciliation record
        extras["health"]["ranks"] = metrics.host["per_rank"]
        extras["host"] = {k: metrics.host[k]
                          for k in ("rank", "processes", "winner_rank")}
    if metrics.host is None and isinstance(scheduler, sched_lib.CompetitiveS):
        extras["competitive_s"] = {
            "ladder": scheduler.ladder,
            "final_sizes": list(scheduler.s_of),
            "windows": len(scheduler.history),
        }
    return FitResult(
        centroids=state.centroids,
        objective=float(state.f_best),
        algorithm="big_means",
        strategy="streaming",
        n_chunks=metrics.chunks_done,
        n_accepted=metrics.accepted,
        n_iterations=metrics.lloyd_iters,
        seed_steps=metrics.seed_steps,
        n_dist_evals=float(state.n_dist_evals),
        wall_time_s=metrics.wall_time_s,
        trace=list(metrics.trace),
        checkpoint_dir=cfg.ckpt_dir,
        config=cfg,
        extras=extras,
    )


def resolve_auto(cfg: BigMeansConfig, source: DataSource) -> str:
    """Pick a concrete strategy from config + data source + topology.

    Out-of-core / stream-shaped sources and stream-loop-only features
    (VNS, ``competitive_s``) go to ``streaming``; ``batch > 1`` goes to
    ``batched``; a mesh or a multi-device host goes to ``sharded``
    (deriving a compatible ``sync_every`` when the requested one does not
    divide the per-worker chunk count — see :func:`_fit_auto`); otherwise
    the paper's ``sequential``.
    """
    from repro.engine import topology as topo_lib

    kind = topo_lib.requested_kind(cfg)
    if kind == "host_mesh":
        return "streaming"          # host_mesh is a streaming-only topology
    worker_kind = kind in ("legacy_mesh", "worker_mesh")
    wants_runner = (cfg.ckpt_dir is not None or cfg.time_budget_s is not None
                    or bool(cfg.vns_ladder)
                    or cfg.scheduler == "competitive_s")
    if not source.in_core or source.prefers_streaming or wants_runner:
        if cfg.ckpt_dir is not None and source.in_core \
                and not source.prefers_streaming and cfg.batch == 1 \
                and not cfg.vns_ladder and cfg.scheduler == "uniform" \
                and worker_kind \
                and cfg.n_chunks % topo_lib.worker_count(cfg) == 0:
            return "sharded"        # in-core mesh + checkpoints: now possible
        return "streaming"
    if cfg.batch > 1:
        return "batched"
    if worker_kind or (kind == "auto" and len(jax.devices()) > 1):
        if cfg.n_chunks % topo_lib.worker_count(cfg) == 0:
            return "sharded"
    return "sequential"


def _fit_auto(cfg: BigMeansConfig, source: DataSource,
              key: jax.Array) -> FitResult:
    from repro.engine import topology as topo_lib

    name = resolve_auto(cfg, source)
    extras = {}
    if name == "sharded":
        workers = topo_lib.worker_count(cfg)
        chunks_per_worker = cfg.n_chunks // workers
        if chunks_per_worker % cfg.sync_every:
            # auto never downgrades a multi-device host to sequential over
            # an incompatible sync_every: derive the largest compatible one
            used = _largest_divisor_le(chunks_per_worker, cfg.sync_every)
            extras["sync_every_adjusted"] = {
                "requested": cfg.sync_every, "used": used}
            cfg = cfg.replace(sync_every=used)
    result = _STRATEGIES[name](cfg, source, key)
    result.extras["auto"] = True
    result.extras.update(extras)
    return result
