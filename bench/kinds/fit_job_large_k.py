"""Traffic kind ``fit_job_large_k``: ``fit_job``'s back-to-back jobs at a k
in the thousands.

The jobs, the window and the checks are ``fit_job``'s.  What differs:

* the float64 quantities of a checked job come from
  ``bench.reference_blocked`` (row blocks; the plain reference would hold
  several ``[s, k]`` float64 arrays, ~10 GB on the host at s = 64000,
  k = 4096);
* two more counters: ``kernel_flops`` and ``kernel_bytes``, the work of
  the Lloyd iterations the jobs required of the kernel (``chunk_traffic``
  at passes = ``n_iterations``: the epilogue's acceptance assign and
  update are left out), and ``seed_steps``, the D^2 steps the jobs'
  K-means++ loops ran (``FitResult.seed_steps``; left out where the
  program has no such counter);
* a job's per-chunk objectives and flags are read from the trace entries
  of its chunks alone: a program that leaves the fused kernel adds a
  ``kernel_fallback`` event there (which ``kernel_demotions`` counts).
"""
from __future__ import annotations

import numpy as np

from bench import reference, reference_blocked, work
from bench.kinds import fit_job

WARM_JOB = fit_job.WARM_JOB


class Kind(fit_job.Kind):
    def __init__(self, cell, seed: int, overrides: dict | None = None):
        super().__init__(cell, seed, overrides)
        self.seed_steps: list = []

    def _run_job(self, index: int) -> fit_job.Job:
        key = self._job_key(index)
        r = self._fit(self.X, self.cfg, method="batched", key=key)
        if index != WARM_JOB:
            self.seed_steps.append(getattr(r, "seed_steps", None))
        chunks = [t for t in r.trace if not isinstance(t[0], str)]
        return fit_job.Job(
            key=key, centroids=r.centroids, f_best=float(r.objective),
            f_new=np.array([t[1] for t in chunks], np.float64),
            accepted=np.array([t[2] for t in chunks], bool),
            n_iterations=int(r.n_iterations), n_chunks=int(r.n_chunks))

    def counters(self) -> dict:
        out = super().counters()
        a = self.algo
        w = [work.chunk_traffic(a["s"], self.dataset["n"], a["k"],
                                a["precision"], j.n_iterations)
             for j in self.jobs]
        out["kernel_flops"] = sum(x["flops"] for x in w)
        out["kernel_bytes"] = sum(x["bytes"] for x in w)
        steps = self.seed_steps[:len(self.jobs)]
        if steps and None not in steps:
            out["seed_steps"] = int(sum(steps))
        return out

    def _incumbent_gaps(self, job: fit_job.Job) -> tuple:
        if not (np.isfinite(job.f_best) and len(job.f_new)
                and np.isfinite(job.centroids).all()):
            return float("inf"), float("inf")
        a = self.algo
        idx = reference.key_index(int(np.argmin(job.f_new)), batch=self.batch,
                                  rounds=self.rounds, devices=self.devices)
        rows = reference.chunk_rows(job.key, idx, s=a["s"],
                                    m=self.dataset["m"], batch=self.batch,
                                    rounds=self.rounds)
        f64, drop = reference_blocked.objective_and_drop64(
            np.asarray(self.X[rows]), job.centroids)
        return abs(job.f_best - f64) / f64, drop
