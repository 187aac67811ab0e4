"""``bench.reference``'s float64 fit quantities, computed in row blocks.

The ``sift1m`` configuration's copy of the reference: the same K-means
objective and one-Lloyd-step drop as :func:`bench.reference.objective64`
and :func:`bench.reference.lloyd_drop64`, but no array larger than
``[rows, k]`` is made.  At s = 64000 and k = 4096 the plain reference
holds several ``[s, k]`` float64 arrays (2 GB each) and a one-hot matrix
of the same size.  The squared distance of a row to its nearest centroid
is ``max(min_j(||c_j||^2 - 2 x.c_j) + ||x||^2, 0)``, which is the plain
reference's ``min_j max(||x||^2 - 2 x.c_j + ||c_j||^2, 0)`` up to the
order of two float64 additions.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

ROWS = 4096


def _nearest(x, c, rows: int) -> tuple:
    """Each row's nearest centroid and its squared distance, in float64."""
    x = np.asarray(x, np.float64)
    c = np.asarray(c, np.float64)
    c2 = (c * c).sum(1)
    ids = np.empty(len(x), np.int64)
    dmin = np.empty(len(x))
    for lo in range(0, len(x), rows):
        xb = x[lo:lo + rows]
        score = xb @ c.T
        score *= -2.0
        score += c2[None, :]
        best = score.argmin(1)
        ids[lo:lo + rows] = best
        dmin[lo:lo + rows] = np.maximum(
            score[np.arange(len(xb)), best] + (xb * xb).sum(1), 0.0)
    return ids, dmin


def objective64(x, c, rows: int = ROWS) -> float:
    """K-means objective ``sum_i min_j ||x_i - c_j||^2`` in float64."""
    return float(_nearest(x, c, rows)[1].sum())


def objective_and_drop64(x, c, rows: int = ROWS) -> tuple:
    """(:func:`objective64`, :func:`lloyd_drop64`) of ``c`` on ``x`` in two
    passes over the distances, not three."""
    x = np.asarray(x, np.float64)
    c = np.asarray(c, np.float64)
    ids, d = _nearest(x, c, rows)
    f0 = d.sum()
    counts = np.bincount(ids, minlength=len(c)).astype(np.float64)
    sums = np.zeros_like(c)
    np.add.at(sums, ids, x)
    moved = np.where(counts[:, None] > 0,
                     sums / np.maximum(counts, 1.0)[:, None], c)
    f1 = objective64(x, moved, rows)
    return float(f0), float((f0 - f1) / f0)


def lloyd_drop64(x, c, rows: int = ROWS) -> float:
    """Relative drop of the float64 objective after one float64 Lloyd step
    from ``c`` on ``x`` (see ``bench.reference.lloyd_drop64``): each row to
    its nearest centroid, each centroid with rows to their mean."""
    return objective_and_drop64(x, c, rows)[1]
