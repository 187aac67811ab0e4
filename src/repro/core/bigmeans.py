"""Big-means (Algorithm 3): the jitted chunk-step core and its state algebra.

This module owns the *numerics* every execution composition reuses
unchanged: :func:`chunk_step` / :func:`chunk_step_batched` (re-seed
degenerate slots, Lloyd, keep-the-best, n_d accounting), the
``BigMeansState`` algebra (:func:`broadcast_state` / :func:`reduce_state` /
the incumbent-exchange helpers) and the uniform :func:`sample_chunk`
decomposition sampler.

The chunk *loops* live in :mod:`repro.engine` — one scheduler / topology /
sync-policy core instead of four hand-rolled drivers.  The historical
entry points remain as thin assemblies of engine pieces, with bit-identical
trajectories:

* :func:`big_means` — the paper's sequential algorithm
  (:func:`repro.engine.incore.sequential`).
* :func:`big_means_batched` — B incumbent streams on one device, optionally
  stream-mesh sharded (``engine.incore.batched_local`` /
  ``batched_stream_mesh``).  ``batch=1`` follows the same key schedule and
  chunk stream as :func:`big_means` (fp-identical on the reference path).
* :func:`big_means_sharded` — multi-worker chunk streams with a periodic
  argmin-all-reduce exchange (``engine.incore.worker_sharded``).
  ``sync_every=1`` is the "collective" mode, ``sync_every=n_chunks`` the
  "competitive" mode; world size 1 recovers the paper exactly.
* ``repro.cluster.runner`` — the out-of-core host loop
  (``engine.stream.run_stream`` + the default middleware stack).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro import spans
from repro.core import kmeans, kmeanspp


class BigMeansState(NamedTuple):
    centroids: jax.Array     # [k, n] f32 — incumbent C
    degenerate: jax.Array    # [k] bool  — degeneracy mask of the incumbent
    f_best: jax.Array        # scalar f32 — f(C, P_C) on the incumbent's chunk
    n_accepted: jax.Array    # scalar i32
    n_dist_evals: jax.Array  # scalar f32 — paper's n_d counter (analytic)


class ChunkInfo(NamedTuple):
    f_new: jax.Array
    accepted: jax.Array
    lloyd_iters: jax.Array
    n_degenerate: jax.Array
    # D^2 steps the K-means++ loop ran for the chunk: k where the chunk step
    # seeded (any slot degenerate; in a batched step, in any stream), else 0.
    # None only where a ChunkInfo is built without it.
    seed_steps: jax.Array | None = None


def init_state(k: int, n: int) -> BigMeansState:
    return BigMeansState(
        centroids=jnp.zeros((k, n), jnp.float32),
        degenerate=jnp.ones((k,), bool),
        f_best=jnp.float32(jnp.inf),
        n_accepted=jnp.int32(0),
        n_dist_evals=jnp.float32(0.0),
    )


@functools.partial(
    jax.jit,
    static_argnames=("max_iters", "tol", "candidates", "impl", "precision"),
)
def chunk_step(
    points: jax.Array,
    state: BigMeansState,
    key: jax.Array,
    *,
    max_iters: int = 300,
    tol: float = 1e-4,
    candidates: int = 3,
    impl: str = "auto",
    precision: str = "auto",
) -> tuple[BigMeansState, ChunkInfo]:
    """Process one chunk P (Algorithm 3, lines 5-12)."""
    k = state.centroids.shape[0]
    s = points.shape[0]

    # line 7: re-initialize degenerate centroids with K-means++ on this chunk.
    # Seeding is the identity when no slot is degenerate, so the whole probe
    # loop is skipped at runtime in that (steady-state) case — on CPU the
    # D^2 probes are the dominant per-chunk cost.
    with jax.named_scope(spans.FIT_SEED):
        seeds = jnp.any(state.degenerate)
        c_init = jax.lax.cond(
            seeds,
            lambda: kmeanspp.seed(
                points, key, k,
                init=state.centroids,
                degenerate=state.degenerate,
                candidates=candidates,
            ),
            lambda: state.centroids.astype(jnp.float32),
        )
    # line 8: local search
    with jax.named_scope(spans.FIT_LLOYD):
        res = kmeans.lloyd(points, c_init, max_iters=max_iters, tol=tol,
                           impl=impl, precision=precision)

    # lines 9-11: keep the best (objectives of equal-size chunks compared)
    with jax.named_scope(spans.FIT_KEEP):
        accepted = res.objective < state.f_best
        n_deg = jnp.sum(state.degenerate)
        n_d = state.n_dist_evals + jnp.float32(s) * (
            jnp.float32(k) * (res.iterations + 2)
            + jnp.float32(candidates) * n_deg
        )
        new_state = BigMeansState(
            centroids=jnp.where(accepted, res.centroids, state.centroids),
            degenerate=jnp.where(accepted, res.degenerate, state.degenerate),
            f_best=jnp.where(accepted, res.objective, state.f_best),
            n_accepted=state.n_accepted + accepted.astype(jnp.int32),
            n_dist_evals=n_d,
        )
        info = ChunkInfo(
            f_new=res.objective,
            accepted=accepted,
            lloyd_iters=res.iterations,
            n_degenerate=jnp.sum(res.degenerate),
            seed_steps=jnp.where(seeds, jnp.int32(k), jnp.int32(0)),
        )
    return new_state, info


LANES = 128   # lanes of a TPU vector register: the minor tile of an array


def packed_width(n: int) -> int | None:
    """Lanes a point takes in :func:`pack_rows`'s copy: the next power of
    two >= ``n`` for a lane-sparse width (n < 128), else None."""
    if n >= LANES:
        return None
    return 1 << (n - 1).bit_length()


def packed_rows(m: int, n: int) -> int:
    """Rows of :func:`pack_rows`' copy of an ``[m, n]`` dataset: ceil(m / g)
    rounded up to a multiple of 128, g = 128 // w points to a row."""
    g = LANES // packed_width(n)
    return -(-m // (g * LANES)) * LANES


def pack_rows(X: jax.Array) -> jax.Array:
    """Point-major packed copy of a lane-sparse dataset ``X [m, n]``.

    ``[R, 128]``, R = :func:`packed_rows`, with g = 128 // w points to a
    row and w = :func:`packed_width` lanes to a point: point i is in row
    i % R, lanes (i // R) * w onward, its features padded with zeros (the
    slots past point m - 1 are zeros).  Each row is lane-dense, so the copy
    is laid out row-major and one point is one sublane of one tile.  XLA
    lays a narrow ``[m, n]`` out feature-major instead (each (8, 128) tile
    holds 8 features of 128 points), where gathering a point reads
    ceil(n / 8) tiles for n values.

    Built as the transpose of a ``[128, R]`` array that stacks the g
    blocks of R points of the feature-major dataset, so that no
    intermediate is lane-sparse.
    """
    m, n = X.shape
    w = packed_width(n)
    R = packed_rows(m, n)
    row_major = Layout(major_to_minor=(0, 1))
    with jax.named_scope(spans.FIT_SAMPLE):
        xt = X.T
        blocks = []
        for j in range(LANES // w):
            block = xt[:, min(j * R, m):min((j + 1) * R, m)]
            blocks.append(jnp.pad(
                block, ((0, w - n), (0, R - block.shape[1]))))   # [w, R]
        stacked = jnp.concatenate(blocks, axis=0)             # [128, R]
        stacked = with_layout_constraint(stacked, row_major)
        return with_layout_constraint(stacked.T, row_major)


def _gather_packed(packed: jax.Array, idx: jax.Array, n: int) -> jax.Array:
    """Rows ``idx`` of the dataset that ``packed`` (:func:`pack_rows`) holds:
    one packed row per point, then its w-lane group picked by selects,
    so every value is an exact copy."""
    w = packed_width(n)
    R = packed.shape[0]
    rows = packed.at[idx % R].get(mode="promise_in_bounds")
    group = (idx // R)[:, None]
    out = rows[:, :n]
    for j in range(1, LANES // w):
        out = jnp.where(group == j, rows[:, j * w:j * w + n], out)
    return with_layout_constraint(out, Layout(major_to_minor=(0, 1)))


def sample_chunk(
    X: jax.Array, key: jax.Array, s: int, *, with_replacement: bool = True,
    packed: jax.Array | None = None,
) -> jax.Array:
    """Uniform random chunk of s rows (the paper's decomposition sampler).

    With replacement by default: for s << m the two schemes are statistically
    indistinguishable and the replacement-free path costs an O(m) permutation.

    ``packed``, :func:`pack_rows` of ``X``, gathers the rows from that copy
    instead of from ``X``: the same indices, the same values.
    """
    m, n = X.shape
    with jax.named_scope(spans.FIT_SAMPLE):
        if with_replacement:
            idx = jax.random.randint(key, (s,), 0, m)
        else:
            idx = jax.random.choice(key, m, (s,), replace=False)
        if packed is None:
            return jnp.take(X, idx, axis=0)
        return _gather_packed(packed, idx, n)


def big_means(
    X: jax.Array,
    key: jax.Array,
    *,
    k: int,
    s: int,
    n_chunks: int,
    max_iters: int = 300,
    tol: float = 1e-4,
    candidates: int = 3,
    impl: str = "auto",
    with_replacement: bool = True,
    precision: str = "auto",
) -> tuple[BigMeansState, ChunkInfo]:
    """Sequential Big-means over an in-core dataset.  Returns (state, traces).

    Assembly shim: single-device topology, uniform schedule, scalar stream
    (:func:`repro.engine.incore.sequential`).
    """
    from repro.engine import incore

    return incore.sequential(
        X, key, k=k, s=s, n_chunks=n_chunks, max_iters=max_iters, tol=tol,
        candidates=candidates, impl=impl, with_replacement=with_replacement,
        precision=precision, gather=incore.gather_for(X, precision, s))


# ---------------------------------------------------------------------------
# Batched (single-device) chunk parallelism: B incumbent streams advance
# through Lloyd concurrently — the in-core analogue of the sharded driver's
# per-worker streams, with the argmin-exchange done by a gather instead of a
# collective.
# ---------------------------------------------------------------------------


def broadcast_state(state: BigMeansState, batch: int) -> BigMeansState:
    """Tile one incumbent into B streams; the stream counters start at zero
    so :func:`reduce_state` can re-aggregate them onto a base state."""
    zeroed = state._replace(
        n_accepted=jnp.int32(0), n_dist_evals=jnp.float32(0.0)
    )
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (batch,) + jnp.shape(a)), zeroed
    )


def reduce_state(
    states: BigMeansState, base: BigMeansState | None = None
) -> BigMeansState:
    """Argmin-reduce B streams into one incumbent (in-core `_exchange_best`,
    degenerate mask included).  Counters are summed across streams — they
    count work done, not who won — and added onto ``base`` when given."""
    with jax.named_scope(spans.FIT_KEEP):
        winner = jnp.argmin(states.f_best)
        n_acc = jnp.sum(states.n_accepted)
        n_d = jnp.sum(states.n_dist_evals)
        if base is not None:
            n_acc = n_acc + base.n_accepted
            n_d = n_d + base.n_dist_evals
        return BigMeansState(
            centroids=states.centroids[winner],
            degenerate=states.degenerate[winner],
            f_best=states.f_best[winner],
            n_accepted=n_acc,
            n_dist_evals=n_d,
        )


def _sync_streams(states: BigMeansState) -> BigMeansState:
    """Give every stream the winner's incumbent; counters stay per-stream."""
    with jax.named_scope(spans.FIT_KEEP):
        winner = jnp.argmin(states.f_best)
        batch = states.f_best.shape[0]

        def tile(a):
            return jnp.broadcast_to(a[winner], (batch,) + a.shape[1:])

        return states._replace(
            centroids=tile(states.centroids),
            degenerate=tile(states.degenerate),
            f_best=tile(states.f_best),
        )


@functools.partial(
    jax.jit,
    static_argnames=("max_iters", "tol", "candidates", "impl", "precision"),
)
def chunk_step_batched(
    points: jax.Array,
    states: BigMeansState,
    keys: jax.Array,
    *,
    max_iters: int = 300,
    tol: float = 1e-4,
    candidates: int = 3,
    impl: str = "auto",
    precision: str = "auto",
) -> tuple[BigMeansState, ChunkInfo]:
    """Process B chunks against B incumbent streams in one fused step.

    points [B, s, n], states with leading batch axis, keys [B, ...].  Per
    stream this is exactly :func:`chunk_step` (re-seed degenerate slots,
    Lloyd, keep-the-best, n_d accounting); across streams everything — the
    K-means++ probes, the Lloyd iterations, the final evaluation — runs as
    one batched computation.
    """
    k = states.centroids.shape[1]
    s = points.shape[1]

    # Same runtime skip as `chunk_step`: when no stream has a degenerate
    # slot (the steady state) the batched probe loop is bypassed entirely.
    with jax.named_scope(spans.FIT_SEED):
        seeds = jnp.any(states.degenerate)
        c_init = jax.lax.cond(
            seeds,
            lambda: kmeanspp.seed_batched(
                points, keys, k,
                init=states.centroids,
                degenerate=states.degenerate,
                candidates=candidates,
            ),
            lambda: states.centroids.astype(jnp.float32),
        )
    with jax.named_scope(spans.FIT_LLOYD):
        res = kmeans.lloyd_batched(
            points, c_init, max_iters=max_iters, tol=tol, impl=impl,
            precision=precision,
        )

    with jax.named_scope(spans.FIT_KEEP):
        accepted = res.objective < states.f_best                # [B]
        n_deg = jnp.sum(states.degenerate, axis=1)              # [B]
        n_d = states.n_dist_evals + jnp.float32(s) * (
            jnp.float32(k) * (res.iterations + 2)
            + jnp.float32(candidates) * n_deg
        )
        new_states = BigMeansState(
            centroids=jnp.where(
                accepted[:, None, None], res.centroids, states.centroids),
            degenerate=jnp.where(
                accepted[:, None], res.degenerate, states.degenerate),
            f_best=jnp.where(accepted, res.objective, states.f_best),
            n_accepted=states.n_accepted + accepted.astype(jnp.int32),
            n_dist_evals=n_d,
        )
        info = ChunkInfo(
            f_new=res.objective,
            accepted=accepted,
            lloyd_iters=res.iterations,
            n_degenerate=jnp.sum(res.degenerate, axis=1),
            seed_steps=jnp.broadcast_to(
                jnp.where(seeds, jnp.int32(k), jnp.int32(0)),
                res.iterations.shape),
        )
    return new_states, info


def big_means_batched(
    X: jax.Array,
    key: jax.Array,
    *,
    k: int,
    s: int,
    batch: int,
    rounds: int,
    sync_every: int = 1,
    max_iters: int = 300,
    tol: float = 1e-4,
    candidates: int = 3,
    impl: str = "auto",
    with_replacement: bool = True,
    precision: str = "auto",
    mesh=None,
    stream_axis: str = "streams",
) -> tuple[BigMeansState, ChunkInfo]:
    """Batched Big-means: B incumbent streams over ``rounds`` chunk rounds.

    Each round samples a ``[batch, s, n]`` chunk batch and advances all
    streams through one :func:`chunk_step_batched`; every ``sync_every``
    rounds the streams exchange incumbents (argmin-reduce, every stream
    continues from the winner).  Returns the final reduced incumbent and a
    ``[rounds * batch]`` trace.  ``batch=1`` recovers the sequential
    :func:`big_means` with ``n_chunks=rounds`` — same key schedule, same
    chunks, same incumbent trajectory (fp-identical on the reference
    path; under the Pallas kernels the batched variant agrees to kernel
    fp tolerance).

    With ``mesh`` (a 1-axis mesh named ``stream_axis``), the stream axis is
    sharded across the mesh devices: each device advances ``batch / ndev``
    streams and the periodic exchange goes through an argmin-all-gather —
    independent chunk streams are exactly the parallelism the paper's
    properties 6-7 promise, so extra devices scale throughput without
    changing the per-stream trajectories (same key schedule as the
    single-device batched driver).

    Assembly shim: uniform schedule + periodic sync on the single-device or
    stream-mesh topology (``repro.engine.incore.batched_local`` /
    ``batched_stream_mesh``).
    """
    from repro.engine import incore

    assert rounds % sync_every == 0, "sync_every must divide rounds"
    devices = 1 if mesh is None else mesh.shape[stream_axis]
    gather = incore.gather_for(X, precision, batch // devices * s)
    if mesh is not None:
        return incore.batched_stream_mesh(
            X, key, mesh=mesh, stream_axis=stream_axis, k=k, s=s,
            batch=batch, rounds=rounds, sync_every=sync_every,
            max_iters=max_iters, tol=tol, candidates=candidates, impl=impl,
            with_replacement=with_replacement, precision=precision,
            gather=gather,
        )
    return incore.batched_local(
        X, key, k=k, s=s, batch=batch, rounds=rounds, sync_every=sync_every,
        max_iters=max_iters, tol=tol, candidates=candidates, impl=impl,
        with_replacement=with_replacement, precision=precision, gather=gather,
    )


def _exchange_best(state: BigMeansState, axis: str) -> BigMeansState:
    """Keep-the-best across workers: tiny argmin-all-reduce on (f, C)."""
    with jax.named_scope(spans.FIT_KEEP):
        f_all = jax.lax.all_gather(state.f_best, axis)        # [W]
        winner = jnp.argmin(f_all)
        c_all = jax.lax.all_gather(state.centroids, axis)     # [W, k, n]
        deg_all = jax.lax.all_gather(state.degenerate, axis)  # [W, k]
        return state._replace(
            centroids=c_all[winner],
            degenerate=deg_all[winner],
            f_best=f_all[winner],
        )


def big_means_sharded(
    X: jax.Array,
    key: jax.Array,
    *,
    mesh,
    k: int,
    s: int,
    chunks_per_worker: int,
    sync_every: int = 1,
    axes: tuple[str, ...] = ("data",),
    max_iters: int = 300,
    tol: float = 1e-4,
    candidates: int = 3,
    impl: str = "auto",
    with_replacement: bool = True,
    precision: str = "auto",
) -> tuple[BigMeansState, ChunkInfo]:
    """Multi-worker Big-means: X row-sharded over ``axes``; per-worker chunk
    streams with periodic incumbent exchange.

    Each worker samples chunks from its local shard (uniform placement makes
    local sampling equivalent to global sampling).  PRNG keys are folded with
    the worker index, so results are reproducible for a fixed topology.

    Assembly shim: worker-partitioned schedule + periodic sync on the
    worker-mesh topology (:func:`repro.engine.incore.worker_sharded`).
    """
    from repro.engine import incore

    workers = math.prod(mesh.shape[a] for a in axes)
    return incore.worker_sharded(
        X, key, mesh=mesh, k=k, s=s, chunks_per_worker=chunks_per_worker,
        sync_every=sync_every, axes=axes, max_iters=max_iters, tol=tol,
        candidates=candidates, impl=impl, with_replacement=with_replacement,
        precision=precision,
        gather=incore.gather_for(X, precision, s, shards=workers))
