"""Traffic kind ``open_loop``: requests sent on a schedule to ``serve()``.

Set-up makes the served centroids on the device from the seed (K-means++ on
one ``s``-row chunk of the configuration's surrogate), starts the server
with the configuration's ``serve`` settings (every shape bucket compiled at
registration) and makes every request's payload before the window opens.

The mix gives the offered ``rate_per_s`` and the request sizes
(log-uniform integers in ``[size_min, size_max]``).  A run of ``seconds``
offers ``round(rate * seconds)`` requests.  Every seed gets the same
multiset of sizes and of inter-arrival gaps (Poisson gaps drawn once with
``multiset_seed``, scaled to span the window exactly), in an order drawn
from the seed; the payload rows are drawn from the seed.

Each request is timed from its due time to the callback that resolves its
future; a request that fails counts as above every limit.
"""
from __future__ import annotations

import functools
import threading
import time

import numpy as np

from bench import data, reference, stats

MODEL = "model"
MISSING, OK = 0, 1
DRAIN_S = 60.0


class Kind:
    def __init__(self, cell, seed: int, overrides: dict | None = None):
        self.cell = cell
        self.seed = int(seed)
        self.dataset = dict(cell.config["dataset"])
        self.algo = dict(cell.config["algorithm"])
        self.serve_cfg = dict(cell.config["serve"], **(overrides or {}))
        self.limits = dict(cell.config["limits"])
        self.mix = dict(cell.mix)

    # -- set-up ---------------------------------------------------------------
    def schedule(self, seconds: float) -> tuple:
        """(due offsets in seconds, sizes) of one run: fixed multisets in
        the seed's order."""
        n = max(1, round(self.mix["rate_per_s"] * seconds))
        base = np.random.default_rng(self.mix["multiset_seed"])
        gaps = base.exponential(1.0, n)
        gaps *= seconds / gaps.sum()
        lo, hi = self.mix["size_min"], self.mix["size_max"]
        sizes = np.floor(np.exp(base.uniform(np.log(lo), np.log(hi + 1), n)))
        sizes = np.clip(sizes, lo, hi).astype(np.int64)
        order = np.random.default_rng(self.seed)
        return (np.cumsum(gaps[order.permutation(n)]),
                sizes[order.permutation(n)])

    def setup(self, seconds: float) -> None:
        from repro.api import ServeConfig, serve

        d, a = self.dataset, self.algo
        self._gen = dict(components=d["components"], spread=d["spread"],
                         noise=d["noise"])
        self._mixture = data.seed_key(self.seed, 0)
        chunk = data.gmm_rows(data.seed_key(self.seed, 1), self._mixture,
                              a["s"], d["n"], **self._gen)
        cents = data.kmeanspp(data.seed_key(self.seed, 2), chunk, a["k"])
        self.centroids = np.asarray(cents)
        self.server = serve({MODEL: cents}, ServeConfig(
            **dict({"precision": a["precision"]}, **self.serve_cfg)))
        self.prepare(seconds)
        for b in self.server.config.buckets():     # one request per bucket
            self.server.assign(MODEL, self.rows[:min(b, len(self.rows))])

    def prepare(self, seconds: float, stream: int = 3) -> None:
        """Make the schedule and every payload of a window of ``seconds``
        at the mix's current rate."""
        import jax

        self.due, self.sizes = self.schedule(seconds)
        rows = data.gmm_rows(data.seed_key(self.seed, stream), self._mixture,
                             int(self.sizes.sum()), self.dataset["n"],
                             **self._gen)
        self.rows = np.asarray(jax.block_until_ready(rows))
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)])

    # -- window ---------------------------------------------------------------
    def _code(self, outcome: str) -> int:
        return self._codes.setdefault(outcome, len(self._codes))

    def _done(self, i: int, fut) -> None:
        """Future callback (the batcher's thread): keep the answer in the
        preallocated arrays, so the window retains no per-request objects."""
        t = time.monotonic()
        exc = fut.exception()
        if exc is not None:
            code = self._code(type(exc).__name__)
        else:
            r = fut.result()
            lo, hi = self.offsets[i], self.offsets[i + 1]
            if np.shape(r.ids) == (hi - lo,) and np.shape(r.dists) == (hi - lo,):
                self.ids[lo:hi] = r.ids
                self.dists[lo:hi] = r.dists
                self.server_ms[i] = r.latency_ms
                code = OK
            else:
                code = self._code("malformed")
        self.t_done[i] = t
        self.status[i] = code
        with self._lock:
            self._resolved += 1
            if self._resolved == self._expected:
                self._all_done.set()

    def window(self, seconds: float) -> None:
        from jax.profiler import TraceAnnotation

        n = len(self.due)
        self.t_done = np.full(n, np.nan)
        self.t_submit = np.full(n, np.nan)
        self.server_ms = np.full(n, np.nan)
        self.status = np.full(n, MISSING, np.int64)
        self.ids = np.full(len(self.rows), -1, np.int64)
        self.dists = np.full(len(self.rows), np.nan, np.float64)
        self._codes = {"missing": MISSING, "ok": OK}
        self._lock = threading.Lock()
        self._all_done = threading.Event()
        self._resolved, self._expected = 0, n + 1
        self.stats0 = self.server.stats(MODEL)
        srv, rows, off = self.server, self.rows, self.offsets
        t0 = time.monotonic()
        due = t0 + self.due
        for i in range(n):
            with TraceAnnotation("bench.wait"):
                while True:
                    dt = due[i] - time.monotonic()
                    if dt <= 0:
                        break
                    time.sleep(dt - 1e-4 if dt > 2e-4 else 0)
            with TraceAnnotation("bench.submit"):
                self.t_submit[i] = time.monotonic()
                try:
                    fut = srv.submit(MODEL, rows[off[i]:off[i + 1]])
                except Exception as exc:  # noqa: BLE001 — counted by kind
                    self.status[i] = self._code(type(exc).__name__)
                    with self._lock:
                        self._resolved += 1
                    continue
                fut.add_done_callback(functools.partial(self._done, i))
        health = srv.health()["models"][MODEL]
        self.queue_at_close = health["queue_depth"]
        with TraceAnnotation("bench.drain"):
            with self._lock:                 # the generator's own count
                self._resolved += 1
                if self._resolved == self._expected:
                    self._all_done.set()
            self._all_done.wait(DRAIN_S)
        self.t0 = t0
        self.stats1 = srv.stats(MODEL)
        self.demoted = list(srv.health()["models"][MODEL]["demoted_buckets"])

    def _names(self) -> dict:
        return {code: name for name, code in self._codes.items()}

    def _due_latency_ms(self) -> np.ndarray:
        ok = self.status == OK
        return (self.t_done - (self.t0 + self.due))[ok] * 1e3

    def end_to_end(self) -> dict:
        lat = self._due_latency_ms()
        n_failed = int((self.status != OK).sum())
        return {f"serve_p{q}_ms": stats.tail(lat, q / 100, n_failed)
                for q in (99, 95, 50)}

    def attempted_failed(self) -> tuple:
        return len(self.due), int((self.status != OK).sum())

    def failures(self) -> dict:
        names = self._names()
        codes, counts = np.unique(self.status[self.status != OK],
                                  return_counts=True)
        return {names[int(c)]: int(k) for c, k in zip(codes, counts)}

    def counters(self) -> dict:
        s0, s1 = self.stats0, self.stats1
        late = (self.t_submit - (self.t0 + self.due)) * 1e3
        return {
            "due_latency_ms": self._due_latency_ms(),
            "n_failed": int((self.status != OK).sum()),
            "server_latency_ms": self.server_ms[self.status == OK],
            "requests": s1["n_requests"] - s0["n_requests"],
            "launches": s1["n_batches"] - s0["n_batches"],
            "gen_late_ms": late[np.isfinite(late)],
            "queue_at_close": self.queue_at_close,
        }

    def release(self) -> None:
        self.server.close()

    # -- correctness ----------------------------------------------------------
    def check(self) -> list:
        """Every answered request's ids and distances against the float64
        nearest centroid; a request never answered fails the run."""
        from repro.kernels import ops

        answered = np.repeat(self.status == OK, self.sizes)
        id_gap = dist_err = 0.0
        x_all = self.rows[answered]
        ids, dists = self.ids[answered], self.dists[answered]
        for lo in range(0, len(x_all), 1 << 16):
            g, e = reference.assignment_gaps(
                x_all[lo:lo + (1 << 16)], self.centroids,
                ids[lo:lo + (1 << 16)], dists[lo:lo + (1 << 16)])
            id_gap, dist_err = max(id_gap, g), max(dist_err, e)
        names = self._names()
        wrong = sum(int((self.status == c).sum())
                    for c, n in names.items() if n in ("missing", "malformed"))
        return [
            ("serve_id_gap", id_gap, self.limits["serve_id_gap"]),
            ("serve_dist_err", dist_err, self.limits["serve_dist_err"]),
            ("unanswered_or_malformed", wrong, 0),
            ("ref_retries", self.stats1["n_ref_retries"]
             - self.stats0["n_ref_retries"], 0),
            ("demoted_buckets", len(self.demoted), 0),
            ("kernel_demotions", len(ops.kernel_demotions()), 0),
        ]
