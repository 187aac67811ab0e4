"""`FitResult` — the one result type every strategy and baseline returns.

Whatever produced it — a Big-means driver, the streaming runner or a §5
competitor — the caller reads the same fields: centroids, the algorithm's
native objective, the acceptance / Lloyd-iteration / distance-evaluation
telemetry (the paper's ``n_d``), a trace and an optional checkpoint path.
``benchmarks/`` and ``examples/`` compare algorithms only through this.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any


@dataclasses.dataclass
class FitResult:
    """Unified result of one :func:`repro.api.fit` call.

    * ``centroids`` — [k, n] float32 cluster centers.
    * ``objective`` — the algorithm's *native* incumbent objective: for
      Big-means strategies f(C, P) on the winning chunk (a sum over ``s``
      points), for full-data baselines f(C, X).  Use
      :func:`repro.api.evaluate` for a like-for-like full-data comparison.
    * ``algorithm`` — "big_means" or the baseline registry name.
    * ``strategy`` — execution strategy that ran ("sequential", "batched",
      "sharded", "streaming"); None for baselines.
    * ``n_chunks`` — chunks processed (0 for full-data baselines).
    * ``n_accepted`` — incumbent improvements (Big-means keep-the-best).
    * ``n_iterations`` — total Lloyd iterations.
    * ``seed_steps`` — D^2 steps the K-means++ seeding loops ran, summed
      over chunks: k for each chunk whose step seeded (0 for baselines).
    * ``n_dist_evals`` — the paper's analytic n_d counter (NaN where the
      algorithm does not track it).
    * ``trace`` — list of trace entries; Big-means strategies log
      ``(chunk_idx, f_new, accepted)`` triples, the streaming runner logs
      ``(chunk_id, f_best, f_new)`` checkpoints plus the structured fault
      events (``fetch_error``, ``quarantine``, ``budget_drop``,
      ``short_chunk``, ``ckpt_fallback``; ``fit`` appends
      ``kernel_fallback`` — see the README trace-event glossary).
    * ``checkpoint_dir`` — where the run checkpointed, if anywhere.
    * ``config`` — the :class:`repro.api.BigMeansConfig` that ran.
    * ``extras`` — strategy-specific detail (resolved auto strategy, final
      cluster counts, worker topology, ...).
    """

    centroids: Any
    objective: float
    algorithm: str = "big_means"
    strategy: str | None = None
    n_chunks: int = 0
    n_accepted: int = 0
    n_iterations: int = 0
    seed_steps: int = 0
    n_dist_evals: float = math.nan
    wall_time_s: float = 0.0
    trace: list = dataclasses.field(default_factory=list)
    checkpoint_dir: str | None = None
    config: Any = None
    extras: dict = dataclasses.field(default_factory=dict)

    @property
    def health(self) -> dict | None:
        """The run-health summary (streaming strategies): chunk accounting
        (``done + failed + dropped + quarantined == fetched``), checkpoint
        fallbacks and quarantine reasons; ``fit`` adds any
        ``kernel_fallbacks`` taken during the call.  None when the strategy
        does not stream."""
        return self.extras.get("health")

    @property
    def k(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_features(self) -> int:
        return self.centroids.shape[1]

    def to_row(self) -> dict:
        """A flat, JSON-safe record of this fit (the evalsuite/benchmark
        row contract — everything scalar, nothing device-resident)."""
        nd = self.n_dist_evals
        return {
            "algorithm": self.algorithm,
            "strategy": self.strategy,
            "objective": float(self.objective),
            "k": int(self.k),
            "n_features": int(self.n_features),
            "n_chunks": int(self.n_chunks),
            "n_accepted": int(self.n_accepted),
            "n_iterations": int(self.n_iterations),
            "seed_steps": int(self.seed_steps),
            "n_dist_evals": None if math.isnan(nd) else float(nd),
            "wall_time_s": float(self.wall_time_s),
            "fit": self.extras.get("fit"),
        }

    def summary(self) -> str:
        via = f" via {self.strategy}" if self.strategy else ""
        nd = ("n_d=nan" if math.isnan(self.n_dist_evals)
              else f"n_d={self.n_dist_evals:.3e}")
        return (f"{self.algorithm}{via}: f={self.objective:.6e}  "
                f"k={self.k}  chunks={self.n_chunks}  "
                f"accepted={self.n_accepted}  iters={self.n_iterations}  "
                f"{nd}  wall={self.wall_time_s:.2f}s")
