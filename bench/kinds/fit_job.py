"""Traffic kind ``fit_job``: back-to-back ``repro.api.fit`` jobs.

Set-up makes the dataset on the device from the seed and runs one job to
compile and warm every program the window uses.  The window runs jobs until
``seconds`` have passed; each job has its own key (the seed's job key
folded with the job index) and ends when its centroids are ready.  The
mix gives ``rounds`` (chunk rounds per job) and ``devices`` (chips the
streams are spread over: ``stream_mesh`` when more than one); the
configuration's ``batch`` is the number of streams on each chip.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import data, reference, stats, work

WARM_JOB = 0xFFFFFFFF


@dataclasses.dataclass
class Job:
    key: object             # the job's PRNG key (device array)
    centroids: object       # returned centroids (device array)
    f_best: float
    f_new: np.ndarray       # per-chunk objectives, in the fit's trace order
    accepted: np.ndarray    # per-chunk keep-the-best flags, the same order
    n_iterations: int
    n_chunks: int


class Kind:
    def __init__(self, cell, seed: int, overrides: dict | None = None):
        self.cell = cell
        self.seed = int(seed)
        self.dataset = dict(cell.config["dataset"])
        self.algo = dict(cell.config["algorithm"], **(overrides or {}))
        self.limits = dict(cell.config["limits"])
        self.devices = int(cell.mix.get("devices", 1))
        self.rounds = int(cell.mix["rounds"])
        self.batch = int(self.algo["batch"]) * self.devices
        self.n_check = int(cell.mix.get("checked_jobs", 12))
        self.jobs: list[Job] = []
        self.window_s = 0.0

    # -- set-up ---------------------------------------------------------------
    def setup(self, seconds: float) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec, \
            SingleDeviceSharding

        from repro.api import BigMeansConfig, TopologySpec, fit
        from repro.engine import topology as topo_lib

        if self.devices > 1:
            topology = TopologySpec(kind="stream_mesh", devices=self.devices)
            mesh = topo_lib.resolve(topology).mesh
            sharding = NamedSharding(mesh, PartitionSpec())
        else:
            topology = "single"
            sharding = SingleDeviceSharding(jax.devices()[0])
        d = self.dataset
        t0 = time.monotonic()
        self.X = jax.block_until_ready(data.gmm(
            data.seed_key(self.seed, 0), d["m"], d["n"],
            components=d["components"], spread=d["spread"],
            noise=d["noise"], sharding=sharding))
        a = self.algo
        self.cfg = BigMeansConfig(
            k=a["k"], s=a["s"], n_chunks=self.rounds * self.batch,
            batch=self.batch, sync_every=a["sync_every"],
            max_iters=a["max_iters"], tol=a["tol"],
            candidates=a["candidates"], precision=a["precision"],
            with_replacement=a["with_replacement"], topology=topology)
        self._fit = fit
        self._job_base = data.seed_key(self.seed, 1)
        t1 = time.monotonic()
        warm = self._run_job(WARM_JOB)
        self.setup_parts = {"data": t1 - t0, "warm_job": time.monotonic() - t1}
        if not np.isfinite(warm.f_best):
            raise RuntimeError(f"warm-up job returned f_best {warm.f_best}")

    def _job_key(self, index: int):
        import jax

        return jax.random.fold_in(self._job_base, np.uint32(index))

    def _run_job(self, index: int) -> Job:
        key = self._job_key(index)
        r = self._fit(self.X, self.cfg, method="batched", key=key)
        return Job(key=key, centroids=r.centroids,
                   f_best=float(r.objective),
                   f_new=np.array([t[1] for t in r.trace], np.float64),
                   accepted=np.array([t[2] for t in r.trace], bool),
                   n_iterations=int(r.n_iterations), n_chunks=int(r.n_chunks))

    # -- window ---------------------------------------------------------------
    def window(self, seconds: float) -> None:
        from jax.profiler import TraceAnnotation

        t0 = time.monotonic()
        while True:
            with TraceAnnotation("bench.fit_job"):
                self.jobs.append(self._run_job(len(self.jobs)))
            if time.monotonic() - t0 >= seconds:
                break
        self.window_s = time.monotonic() - t0

    def end_to_end(self) -> dict:
        return {"fit_job_s": stats.per_job(self.window_s, len(self.jobs))}

    def attempted_failed(self) -> tuple:
        return len(self.jobs), 0

    def counters(self) -> dict:
        a = self.algo
        flops = nbytes = 0.0
        for j in self.jobs:
            w = work.chunk_traffic(a["s"], self.dataset["n"], a["k"],
                                   a["precision"],
                                   work.job_passes(j.n_iterations, j.n_chunks))
            flops += w["flops"]
            nbytes += w["bytes"]
        return {"jobs": len(self.jobs), "window_s": self.window_s,
                "lloyd_flops": flops, "lloyd_bytes": nbytes}

    def release(self) -> None:
        for j in self.jobs:
            j.centroids = np.asarray(j.centroids)

    # -- correctness ----------------------------------------------------------
    def check(self) -> list:
        """For each sampled job, on the rows of the chunk its trace names as
        the incumbent's: the float64 objective of the returned centroids
        against its ``f_best``, and the drop of that objective after one
        float64 Lloyd step.  For every job: the chunks whose acceptance
        flag differs from keep-the-best replayed over its objectives."""
        from repro.kernels import ops

        rng = np.random.default_rng(self.seed)
        pick = sorted(rng.choice(len(self.jobs),
                                 min(self.n_check, len(self.jobs)),
                                 replace=False))
        obj_rel = drop = 0.0
        for i in pick:
            o, d = self._incumbent_gaps(self.jobs[i])
            obj_rel, drop = max(obj_rel, o), max(drop, d)
        mismatched = sum(self._mismatched(j) for j in self.jobs)
        return [
            ("fit_obj_rel", obj_rel, self.limits["fit_obj_rel"]),
            ("fit_lloyd_drop", drop, self.limits["fit_lloyd_drop"]),
            ("fit_accept_mismatch", mismatched, 0),
            ("kernel_demotions", len(ops.kernel_demotions()), 0),
        ]

    def _incumbent_gaps(self, job: Job) -> tuple:
        if not (np.isfinite(job.f_best) and len(job.f_new)
                and np.isfinite(job.centroids).all()):
            return float("inf"), float("inf")
        a = self.algo
        idx = reference.key_index(int(np.argmin(job.f_new)), batch=self.batch,
                                  rounds=self.rounds, devices=self.devices)
        rows = reference.chunk_rows(job.key, idx, s=a["s"],
                                    m=self.dataset["m"], batch=self.batch,
                                    rounds=self.rounds)
        x = np.asarray(self.X[rows])
        f64 = reference.objective64(x, job.centroids)
        return (abs(job.f_best - f64) / f64,
                reference.lloyd_drop64(x, job.centroids))

    def _mismatched(self, job: Job) -> int:
        if len(job.f_new) != self.rounds * self.batch:
            return max(1, len(job.f_new))
        replay = reference.replay_accepted(
            job.f_new, batch=self.batch, rounds=self.rounds,
            devices=self.devices, sync_every=int(self.algo["sync_every"]))
        return int(np.count_nonzero(replay != job.accepted))
