"""The plain float64 references the benchmark's ``correct`` rests on.

Nothing here imports the program.  The fit reference recomputes, from the
job's key alone, which rows the sampler drew for a chunk (the documented
counter-based schedule: chunk ``(round r, stream b)`` of a job with key
``key`` is ``randint(split(split(key, rounds*batch)[r*batch + b])[0], (s,),
0, m)``), and evaluates the K-means objective of the returned centroids on
those rows in float64, and one float64 Lloyd step from them.  It replays
keep-the-best over the per-chunk objectives the fit reports.  The serving
reference is the nearest centroid in float64.
"""
from __future__ import annotations

import numpy as np


def key_index(trace_index: int, *, batch: int, rounds: int,
              devices: int) -> int:
    """Position in the job's key schedule of the chunk at ``trace_index``
    of the fit's per-chunk trace.

    One device lists chunks round-major (``r*batch + b``).  A stream mesh of
    ``devices`` lists each device's block of ``batch/devices`` streams in
    turn, round-major inside the block.
    """
    local = batch // devices
    dev, rest = divmod(int(trace_index), rounds * local)
    r, b_local = divmod(rest, local)
    return r * batch + dev * local + b_local


def chunk_rows(job_key, index: int, *, s: int, m: int, batch: int,
               rounds: int):
    """Row ids of the chunk at ``index`` of the job's key schedule."""
    import jax

    keys = jax.random.split(job_key, rounds * batch)
    ks = jax.random.split(keys[index])[0]
    return jax.random.randint(ks, (s,), 0, m)


def sqdist64(x, c) -> np.ndarray:
    """``[m, k]`` squared distances in float64."""
    x = np.asarray(x, np.float64)
    c = np.asarray(c, np.float64)
    d = (x * x).sum(1)[:, None] - 2.0 * (x @ c.T) + (c * c).sum(1)[None, :]
    return np.maximum(d, 0.0)


def objective64(x, c) -> float:
    """K-means objective ``sum_i min_j ||x_i - c_j||^2`` in float64."""
    return float(sqdist64(x, c).min(1).sum())


def lloyd_drop64(x, c) -> float:
    """Relative drop of the float64 objective after one float64 Lloyd step
    from ``c`` on ``x``: assign each row to its nearest centroid, move each
    centroid with rows to their mean, and compare the objectives.  Lloyd's
    fixed point reads 0 up to rounding; centroids whose update left them
    short of the means read more.  Never below 0 but for rounding."""
    x = np.asarray(x, np.float64)
    c = np.asarray(c, np.float64)
    d = sqdist64(x, c)
    ids = d.argmin(1)
    f0 = d[np.arange(len(x)), ids].sum()
    onehot = np.zeros((len(x), len(c)))
    onehot[np.arange(len(x)), ids] = 1.0
    counts = onehot.sum(0)
    sums = onehot.T @ x
    moved = np.where(counts[:, None] > 0,
                     sums / np.maximum(counts, 1.0)[:, None], c)
    f1 = sqdist64(x, moved).min(1).sum()
    return float((f0 - f1) / f0)


def replay_accepted(f_new, *, batch: int, rounds: int, devices: int,
                    sync_every: int) -> np.ndarray:
    """Which chunks keep-the-best accepts, replayed from the per-chunk
    objectives in the fit's trace order.

    Each stream's incumbent starts at infinity and takes a chunk's
    objective when it is strictly lower; after every ``sync_every`` rounds
    each stream's incumbent becomes the lowest over the whole fleet (all
    streams on all devices).  Returns the flags in the trace order.
    """
    f_new = np.asarray(f_new, np.float64)
    order = [key_index(i, batch=batch, rounds=rounds, devices=devices)
             for i in range(len(f_new))]
    f = np.full(rounds * batch, np.nan)
    f[order] = f_new
    f = f.reshape(rounds, batch)
    incumbent = np.full(batch, np.inf)
    accepted = np.zeros((rounds, batch), bool)
    for r in range(rounds):
        accepted[r] = f[r] < incumbent
        incumbent = np.where(accepted[r], f[r], incumbent)
        if (r + 1) % sync_every == 0:
            incumbent[:] = incumbent.min()
    return accepted.reshape(-1)[order]


def assignment_gaps(x, c, ids, dists) -> tuple:
    """Widest gaps of served answers against the float64 nearest centroid.

    Returns ``(id_gap, dist_err)``: the largest amount by which a served
    centroid's float64 distance lies above the nearest one's, and the
    largest error of a served distance against the nearest float64
    distance, both over ``||x||^2 + ||c_best||^2`` (the magnitude of the
    terms a distance is assembled from).  An exact tie reads 0.
    """
    x = np.asarray(x, np.float64)
    c = np.asarray(c, np.float64)
    d = sqdist64(x, c)
    best = d.argmin(1)
    rows = np.arange(len(x))
    dmin = d[rows, best]
    scale = np.maximum((x * x).sum(1) + (c * c).sum(1)[best],
                       np.finfo(np.float64).tiny)
    ids = np.asarray(ids, np.int64)
    if ids.shape != best.shape or ids.min(initial=0) < 0 \
            or ids.max(initial=0) >= len(c):
        return float("inf"), float("inf")
    gap = (d[rows, ids] - dmin) / scale
    err = np.abs(np.asarray(dists, np.float64) - dmin) / scale
    err = np.where(np.isnan(err), np.inf, err)      # a NaN answer is wrong
    return float(gap.max(initial=0.0)), float(err.max(initial=0.0))
