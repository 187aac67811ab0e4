"""In-core execution: the jitted chunk-loop cores behind every driver.

These are the (moved, not rewritten) scan bodies of the historical
``big_means`` / ``big_means_batched`` / ``big_means_sharded`` drivers —
parameterized by the engine's orthogonal pieces instead of hard-coding one
composition each:

* the **scheduler** appears as the key schedule (``split(key, rounds*batch)``
  for the uniform schedule, ``fold_in(key, worker_index)`` for the
  worker-partitioned one);
* the **topology** selects the placement (:func:`sequential` /
  :func:`batched_local` on one device, :func:`batched_stream_mesh` /
  :func:`worker_sharded` under ``shard_map``);
* the **sync policy** is the ``sync_every`` static argument.

Trajectories are bit-identical to the pre-engine drivers: same jitted
functions, same static arguments, same key schedules.

:func:`worker_sharded_rounds` is the new piece: the same worker-sharded
window (``sync_every`` chunks per worker, then an argmin exchange) driven
from a *host* loop, one jitted segment per window, so the accept-loop
middleware stack (checkpoint/resume, time budget) composes with the
multi-worker topology — previously impossible.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import spans
from repro.core.bigmeans import (
    LANES,
    BigMeansState,
    ChunkInfo,
    _exchange_best,
    _sync_streams,
    broadcast_state,
    chunk_step,
    chunk_step_batched,
    init_state,
    pack_rows,
    packed_rows,
    packed_width,
    reduce_state,
    sample_chunk,
)
from repro.engine import middleware as mw
from repro.kernels import precision as px

def _cast_dataset(X, precision):
    """Dataset-level storage cast for the in-core drivers.

    int8 is the exception: scales are a *chunk* property (``s[f]`` over the
    chunk's points), so the dataset stays full-width here and each sampled
    chunk is quantized at Lloyd entry — same semantics as the streaming
    prefetcher, which quantizes per fetched chunk.
    """
    if px.resolve(precision, X.dtype) == "int8":
        return jnp.asarray(X, jnp.float32)
    return px.cast_storage(X, precision)


def _packs(gather, n: int) -> bool:
    """Whether chunk rows of width ``n`` are gathered from a packed copy:
    ``gather`` as given, None deciding by the width alone."""
    if gather is None:
        return packed_width(n) is not None
    return gather == "packed"


def _dataset(X, precision, gather):
    """The dataset as the chunk loops sample it: its storage cast, and the
    point-major packed copy that chunk rows are gathered from (or None).
    Made once per call, outside the chunk loop."""
    X = _cast_dataset(X, precision)
    return X, (pack_rows(X) if _packs(gather, X.shape[1]) else None)


def gather_for(X, precision, chunk_rows: int, *, shards: int = 1) -> str:
    """How the functions below gather chunk rows from ``X``: ``'packed'``,
    from :func:`repro.core.bigmeans.pack_rows`'s copy, or ``'rows'``, by
    ``jnp.take`` on the dataset.  The chunks are the same either way.

    Packed where the width is lane-sparse (n < 128) and, where the device
    reports ``bytes_limit``, the dataset, its packed copy, the intermediate
    the copy is built through and a gather of ``chunk_rows`` packed rows
    fit it; ``shards`` is the number of devices the rows are split over.
    Decided on the host when a fit is planned and passed to them as their
    static ``gather``; their default, None, decides by the width alone.
    """
    m, n = X.shape
    if packed_width(n) is None:
        return "rows"
    devices = X.devices() if isinstance(X, jax.Array) else jax.devices()[:1]
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    if None in limits:
        return "packed"
    rows = -(-m // shards)
    resolved = px.resolve(precision, X.dtype)
    storage = jnp.float32 if resolved == "int8" else px.storage_dtype(resolved)
    item = jnp.dtype(storage).itemsize            # as _cast_dataset keeps it
    need = item * (rows * n + (2 * packed_rows(rows, n) + chunk_rows) * LANES)
    return "packed" if need <= min(limits) else "rows"


_shard_map = functools.partial(jax.shard_map, check_vma=False)


# ---------------------------------------------------------------------------
# single-device, scalar stream (the paper's Algorithm 3)
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "s", "n_chunks", "max_iters", "tol", "candidates", "impl",
        "with_replacement", "precision", "gather",
    ),
)
def sequential(
    X, key, *, k, s, n_chunks, max_iters=300, tol=1e-4, candidates=3,
    impl="auto", with_replacement=True, precision="auto", gather=None,
):
    """Sequential Big-means over an in-core dataset.  Returns (state, traces).

    ``gather`` (here and in the functions below) is :func:`gather_for`'s
    choice: the same chunks either way."""
    X, packed = _dataset(X, precision, gather)
    state = init_state(k, X.shape[1])

    def body(carry, key_i):
        state = carry
        ks, kc = jax.random.split(key_i)
        chunk = sample_chunk(X, ks, s, with_replacement=with_replacement,
                             packed=packed)
        state, info = chunk_step(
            chunk, state, kc,
            max_iters=max_iters, tol=tol, candidates=candidates, impl=impl,
            precision=precision,
        )
        return state, info

    keys = jax.random.split(key, n_chunks)
    state, infos = jax.lax.scan(body, state, keys)
    return state, infos


# ---------------------------------------------------------------------------
# single-device, B batched streams (uniform schedule, periodic sync)
# ---------------------------------------------------------------------------


def stream_keys(key, rounds: int, sync_every: int, batch: int):
    """[outer, sync_every, batch, ...] key schedule: chunk (r, b) gets
    split(key, rounds*batch)[r*batch + b] — for batch=1 this is
    byte-identical to the sequential schedule."""
    keys = jax.random.split(key, rounds * batch)
    return keys.reshape(
        (rounds // sync_every, sync_every, batch) + keys.shape[1:])


def stream_scan(X, states, keys, *, s, max_iters, tol, candidates, impl,
                with_replacement, sync_fn, precision="auto", packed=None):
    """Scan ``rounds`` chunk rounds over per-stream states; ``sync_fn``
    exchanges incumbents at each sync boundary.  ``packed``: the dataset's
    packed copy to gather from (:func:`sample_chunk`)."""

    def body(states, keys_i):                       # keys_i [batch, ...]
        with jax.named_scope(spans.FIT_SAMPLE):
            split = jax.vmap(jax.random.split)(keys_i)  # [batch, 2, ...]
        ks, kc = split[:, 0], split[:, 1]
        chunks = jax.vmap(
            lambda kk: sample_chunk(
                X, kk, s, with_replacement=with_replacement, packed=packed)
        )(ks)
        return chunk_step_batched(
            chunks, states, kc,
            max_iters=max_iters, tol=tol, candidates=candidates, impl=impl,
            precision=precision,
        )

    def round_body(states, keys_r):                 # keys_r [sync, batch, ...]
        states, infos = jax.lax.scan(body, states, keys_r)
        return sync_fn(states), infos

    states, infos = jax.lax.scan(round_body, states, keys)
    # [outer, sync, batch, ...] -> [rounds * batch, ...], round-major order
    infos = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[3:]), infos)
    return states, infos


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "s", "batch", "rounds", "sync_every", "max_iters", "tol",
        "candidates", "impl", "with_replacement", "precision", "gather",
    ),
)
def batched_local(
    X, key, *, k, s, batch, rounds, sync_every, max_iters, tol, candidates,
    impl, with_replacement, precision="auto", gather=None,
):
    X, packed = _dataset(X, precision, gather)
    states = broadcast_state(init_state(k, X.shape[1]), batch)
    keys = stream_keys(key, rounds, sync_every, batch)
    states, infos = stream_scan(
        X, states, keys, s=s, max_iters=max_iters, tol=tol,
        candidates=candidates, impl=impl, with_replacement=with_replacement,
        sync_fn=_sync_streams, precision=precision, packed=packed,
    )
    return reduce_state(states), infos


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "stream_axis", "k", "s", "batch", "rounds", "sync_every",
        "max_iters", "tol", "candidates", "impl", "with_replacement",
        "precision", "gather",
    ),
)
def batched_stream_mesh(
    X, key, *, mesh, stream_axis, k, s, batch, rounds, sync_every,
    max_iters, tol, candidates, impl, with_replacement, precision="auto",
    gather=None,
):
    ndev = mesh.shape[stream_axis]
    assert batch % ndev == 0, "stream mesh axis must divide batch"
    X, packed = _dataset(X, precision, gather)
    n = X.shape[1]
    keys = stream_keys(key, rounds, sync_every, batch)

    def sync(states):
        """Global keep-the-best: local winner, then argmin-all-gather
        across devices; every stream continues from the global winner."""
        with jax.named_scope(spans.FIT_KEEP):
            w = jnp.argmin(states.f_best)
            f_all = jax.lax.all_gather(states.f_best[w], stream_axis)  # [D]
            c_all = jax.lax.all_gather(states.centroids[w], stream_axis)
            d_all = jax.lax.all_gather(states.degenerate[w], stream_axis)
            g = jnp.argmin(f_all)
            bl = states.f_best.shape[0]
            return states._replace(
                centroids=jnp.broadcast_to(c_all[g], states.centroids.shape),
                degenerate=jnp.broadcast_to(d_all[g],
                                            states.degenerate.shape),
                f_best=jnp.broadcast_to(f_all[g], (bl,)),
            )

    def worker(x_rep, packed_rep, keys_local):  # keys [outer, sync, batch/D]
        states = broadcast_state(init_state(k, n), keys_local.shape[2])
        states, infos = stream_scan(
            x_rep, states, keys_local, s=s, max_iters=max_iters, tol=tol,
            candidates=candidates, impl=impl,
            with_replacement=with_replacement, sync_fn=sync,
            precision=precision, packed=packed_rep,
        )
        local = reduce_state(states)
        with jax.named_scope(spans.FIT_KEEP):
            f_all = jax.lax.all_gather(local.f_best, stream_axis)
            c_all = jax.lax.all_gather(local.centroids, stream_axis)
            d_all = jax.lax.all_gather(local.degenerate, stream_axis)
            g = jnp.argmin(f_all)
            final = BigMeansState(
                centroids=c_all[g],
                degenerate=d_all[g],
                f_best=f_all[g],
                n_accepted=jax.lax.psum(local.n_accepted, stream_axis),
                n_dist_evals=jax.lax.psum(local.n_dist_evals, stream_axis),
            )
        return final, infos

    shard = _shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(), P(), P(None, None, stream_axis, None)),
        out_specs=(
            BigMeansState(P(), P(), P(), P(), P()),
            ChunkInfo(*([P(stream_axis)] * len(ChunkInfo._fields))),
        ),
    )
    return shard(X, packed, keys)


# ---------------------------------------------------------------------------
# worker mesh: one chunk stream per worker, argmin-all-reduce exchange
# ---------------------------------------------------------------------------


def worker_sharded(
    X, key, *, mesh, k, s, chunks_per_worker, sync_every=1, axes=("data",),
    max_iters=300, tol=1e-4, candidates=3, impl="auto",
    with_replacement=True, precision="auto", gather=None,
):
    """Multi-worker Big-means: X row-sharded over ``axes``; per-worker chunk
    streams with periodic incumbent exchange.

    Each worker samples chunks from its local shard (uniform placement makes
    local sampling equivalent to global sampling).  PRNG keys are folded with
    the worker index, so results are reproducible for a fixed topology.
    """
    from repro.engine.topology import check_axes

    check_axes(mesh, axes)
    assert chunks_per_worker % sync_every == 0, "sync_every must divide chunks"
    n_rounds = chunks_per_worker // sync_every
    axis = axes if len(axes) > 1 else axes[0]

    def worker(x_local, key):
        widx = jax.lax.axis_index(axes[0])
        if len(axes) > 1:
            for a in axes[1:]:
                widx = widx * mesh.shape[a] + jax.lax.axis_index(a)
        key = jax.random.fold_in(key, widx)
        state = init_state(k, x_local.shape[1])
        packed = (pack_rows(x_local) if _packs(gather, x_local.shape[1])
                  else None)

        def round_body(state, key_r):
            def body(state, key_i):
                ks, kc = jax.random.split(key_i)
                chunk = sample_chunk(
                    x_local, ks, s, with_replacement=with_replacement,
                    packed=packed,
                )
                return chunk_step(
                    chunk, state, kc,
                    max_iters=max_iters, tol=tol,
                    candidates=candidates, impl=impl, precision=precision,
                )

            keys = jax.random.split(key_r, sync_every)
            state, infos = jax.lax.scan(body, state, keys)
            state = _exchange_best(state, axis)
            return state, infos

        keys = jax.random.split(key, n_rounds)
        state, infos = jax.lax.scan(round_body, state, keys)
        infos = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), infos)
        # distance-eval counter: aggregate across workers (paper's n_d).
        total_nd = jax.lax.psum(state.n_dist_evals, axis)
        total_acc = jax.lax.psum(state.n_accepted, axis)
        state = state._replace(n_dist_evals=total_nd, n_accepted=total_acc)
        return state, infos

    shard = _shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axes), P()),
        out_specs=(
            BigMeansState(P(), P(), P(), P(), P()),
            ChunkInfo(*([P(axes[0])] * len(ChunkInfo._fields))),
        ),
    )
    xd = _cast_dataset(X, precision)
    return shard(xd, key)


# ---------------------------------------------------------------------------
# worker mesh, host-orchestrated: one jitted segment per sync window, so
# middleware (checkpoint/resume, time budget) runs between windows
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "mesh", "axes", "k", "s", "n_rounds", "sync_every", "max_iters",
        "tol", "candidates", "impl", "with_replacement", "precision",
    ),
)
def _sharded_segment(
    X, packed, key, r, states, *, mesh, axes, k, s, n_rounds, sync_every,
    max_iters, tol, candidates, impl, with_replacement, precision,
):
    """Window ``r`` of the worker-sharded run: ``sync_every`` chunks per
    worker, then the argmin exchange — with the per-worker state stack
    ``[W, ...]`` passed in/out instead of living inside one big scan.

    The key schedule is byte-identical to :func:`worker_sharded`: each
    worker folds its index into the base key, splits ``n_rounds`` round
    keys, and consumes round ``r``'s — so an uninterrupted sequence of
    segments replays the one-shot driver's trajectory exactly.

    ``packed`` is each worker's :func:`pack_rows` of its shard, row-sharded
    like ``X`` (:func:`_pack_shards`), or None.
    """
    axis = axes if len(axes) > 1 else axes[0]

    def worker(x_local, packed_local, key, r, state_stack):
        widx = jax.lax.axis_index(axes[0])
        if len(axes) > 1:
            for a in axes[1:]:
                widx = widx * mesh.shape[a] + jax.lax.axis_index(a)
        kw = jax.random.fold_in(key, widx)
        key_r = jax.random.split(kw, n_rounds)[r]
        state = jax.tree.map(lambda a: a[0], state_stack)   # local stack: [1, ...]

        def body(state, key_i):
            ks, kc = jax.random.split(key_i)
            chunk = sample_chunk(
                x_local, ks, s, with_replacement=with_replacement,
                packed=packed_local)
            return chunk_step(
                chunk, state, kc,
                max_iters=max_iters, tol=tol, candidates=candidates,
                impl=impl, precision=precision,
            )

        keys = jax.random.split(key_r, sync_every)
        state, infos = jax.lax.scan(body, state, keys)
        state = _exchange_best(state, axis)
        return (jax.tree.map(lambda a: a[None], state),
                jax.tree.map(lambda a: a[None], infos))

    shard = _shard_map(
        worker,
        mesh=mesh,
        in_specs=(P(axes), P(axes), P(), P(),
                  BigMeansState(*([P(axes)] * 5))),
        out_specs=(
            BigMeansState(*([P(axes)] * 5)),
            ChunkInfo(*([P(axes[0])] * len(ChunkInfo._fields))),
        ),
    )
    return shard(X, packed, key, r, states)


@functools.partial(jax.jit, static_argnames=("mesh", "axes"))
def _pack_shards(X, *, mesh, axes):
    """:func:`pack_rows` of each worker's row shard of ``X``, row-sharded
    the same way."""
    return _shard_map(pack_rows, mesh=mesh, in_specs=P(axes),
                      out_specs=P(axes))(X)


def worker_sharded_rounds(
    X, key, *, mesh, k, s, chunks_per_worker, sync_every=1, axes=("data",),
    max_iters=300, tol=1e-4, candidates=3, impl="auto",
    with_replacement=True, precision="auto", gather=None, cfg=None,
    middlewares=None, resume=True,
):
    """Worker-sharded Big-means with the accept loop on the host.

    Functionally :func:`worker_sharded` (bit-identical trajectories when no
    middleware interrupts), but each sync window is one jitted segment and
    the middleware stack runs between windows — enabling sharded +
    checkpoint/resume and sharded + time-budget compositions.

    Returns ``(state, infos, ctx)``; ``state`` is the reduced incumbent,
    ``infos`` the worker-major chunk trace of the windows that ran.
    """
    from repro.engine.topology import check_axes

    check_axes(mesh, axes)
    assert chunks_per_worker % sync_every == 0, "sync_every must divide chunks"
    n_rounds = chunks_per_worker // sync_every
    W = 1
    for a in axes:
        W *= int(mesh.shape[a])
    xd = _cast_dataset(X, precision)
    n = X.shape[1]
    packed = (_pack_shards(xd, mesh=mesh, axes=tuple(axes))
              if _packs(gather, n) else None)

    stack = mw.MiddlewareStack(middlewares or [])
    states = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (W,) + a.shape), init_state(k, n))
    ctx = mw.EngineContext(cfg=cfg, key=key, metrics=None, state=states,
                           t0=time.monotonic(), last_s=s)
    ckpt = stack.find(mw.Checkpoint)
    start_round = 0
    if resume and ckpt is not None and ckpt.maybe_restore(ctx, states):
        start_round = ctx.step
        states, key = ctx.state, ctx.key
    if start_round >= n_rounds:
        start_round = n_rounds
    stack.on_start(ctx)

    window_infos = []
    for r in range(start_round, n_rounds):
        states, infos = _sharded_segment(
            xd, packed, key, jnp.int32(r), states,
            mesh=mesh, axes=tuple(axes), k=k, s=s, n_rounds=n_rounds,
            sync_every=sync_every, max_iters=max_iters, tol=tol,
            candidates=candidates, impl=impl,
            with_replacement=with_replacement, precision=precision,
        )
        ctx.state, ctx.info = states, infos
        ctx.step = r + 1
        ctx.last_cid = (r + 1) * sync_every - 1
        window_infos.append(infos)
        stack.after_window(ctx)
        if stack.should_stop(ctx):
            break

    stack.on_finish(ctx)
    # reduce: post-exchange incumbents are replicated across workers; the
    # counters are per-worker and sum to the paper's global n_d / accepts.
    final = BigMeansState(
        centroids=states.centroids[0],
        degenerate=states.degenerate[0],
        f_best=states.f_best[0],
        n_accepted=jnp.sum(states.n_accepted),
        n_dist_evals=jnp.sum(states.n_dist_evals),
    )
    if window_infos:
        # [rounds][Wd, sync] -> [Wd, rounds, sync] -> worker-major flat,
        # matching the one-shot driver's trace order.
        infos = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=1).reshape(
                (-1,) + xs[0].shape[2:]),
            *window_infos)
    else:
        infos = jax.tree.map(
            lambda a: jnp.zeros((0,) + a.shape[1:], a.dtype),
            _zero_infos(k))
    return final, infos, ctx


def _zero_infos(k):
    return ChunkInfo(
        f_new=jnp.zeros((1,), jnp.float32),
        accepted=jnp.zeros((1,), bool),
        lloyd_iters=jnp.zeros((1,), jnp.int32),
        n_degenerate=jnp.zeros((1,), jnp.int32),
        seed_steps=jnp.zeros((1,), jnp.int32),
    )
