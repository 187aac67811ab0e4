"""Chaos tier: the quick reproduction suite under a seeded FaultPlan.

For each quick-tier dataset the harness runs the streaming strategy twice
over bitwise-identical chunk streams:

* a fault-free run — and, midway, a staged checkpointed prefix whose
  newest checkpoint is then *truncated* (a torn write);
* a chaos run resuming over that corrupted checkpoint directory, with a
  seeded :class:`repro.engine.faults.FaultPlan` injecting ~10% transient
  fetch faults (recovered by ``retries=2``) plus one NaN-poisoned chunk
  (quarantined).

The run must then prove the fault-tolerance contract end-to-end:

1. it completes, and chunk accounting reconciles exactly
   (``done + failed + dropped + quarantined == fetched``);
2. the incumbent objective stays finite and monotone non-increasing;
3. restore healed past the torn write (``ckpt_fallback`` in the trace);
4. quality holds: ``eps_chaos - eps_clean <= --eps-tol`` (the same
   tolerance the suite gate applies to baseline drift).

Exit status is non-zero on any violation, so CI can gate on it::

    PYTHONPATH=src python -m benchmarks.chaos --seed 0
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trajectory_monotone(trace) -> bool:
    """The streaming runner's checkpoint entries carry f_best; with a fixed
    chunk size the raw incumbent must never rise."""
    fs = [t[1] for t in trace
          if len(t) == 3 and isinstance(t[0], (int, np.integer))]
    return all(b <= a * (1.0 + 1e-4) for a, b in zip(fs, fs[1:]))


def run_cell(spec, *, seed: int, data_root: str | None, eps_tol: float,
             retries: int = 2) -> dict:
    from repro.api import BigMeansConfig, evaluate, fit
    from repro.cluster import runner
    from repro.engine import faults
    from repro.evalsuite import datasets, metrics

    src = datasets.source(spec, data_root)
    provider = src.provider(spec.s, seed=seed)
    cfg = BigMeansConfig(k=spec.k, s=spec.s, n_chunks=spec.n_chunks,
                         prefetch=2, seed=seed,
                         retries=retries, retry_backoff_s=0.0,
                         validate_chunks=True)

    clean = fit(provider, cfg, method="streaming", n_features=src.n_features)
    _, f_clean = evaluate(clean, src)
    eps_clean = metrics.relative_error(f_clean, spec.f_star)

    # Stage a checkpointed prefix of the same stream, then tear its newest
    # checkpoint so the chaos run has to self-heal on resume.
    ckpt_dir = tempfile.mkdtemp(prefix=f"chaos-{spec.name}-")
    stage = cfg.replace(n_chunks=spec.n_chunks // 2,
                        ckpt_dir=ckpt_dir, ckpt_every=spec.n_chunks // 4)
    runner.run(provider, stage, n_features=src.n_features)
    faults.corrupt_checkpoint(ckpt_dir)

    # ~10% transient fetch faults everywhere + one poisoned chunk in the
    # post-resume tail (an earlier id would be skipped by the resume).
    plan = faults.FaultPlan(seed=seed + 0xC4A05, transient_rate=0.10,
                            transient_attempts=1,
                            nan_ids=(spec.n_chunks - 3,))
    wrapped = plan.wrap(provider)
    chaos = fit(wrapped, cfg.replace(ckpt_dir=ckpt_dir,
                                     ckpt_every=spec.n_chunks // 4),
                method="streaming", n_features=src.n_features)
    _, f_chaos = evaluate(chaos, src)
    eps_chaos = metrics.relative_error(f_chaos, spec.f_star)

    h = chaos.health or {}
    fetched = sum(wrapped.attempts.values())
    checks = {
        "completed_finite": bool(np.isfinite(chaos.objective)
                                 and np.isfinite(f_chaos)),
        "accounting_reconciles": (
            h.get("chunks_done", -1) + h.get("chunks_failed", 0)
            + h.get("chunks_dropped", 0) + h.get("chunks_quarantined", 0)
            == h.get("chunks_fetched")),
        "fetch_attempts_consistent": (
            h.get("chunks_fetched", -1) + sum(
                1 for cid in plan.transient_ids(spec.n_chunks)
                if wrapped.attempts[cid] > 1) == fetched),
        "transients_recovered": h.get("chunks_failed") == 0,
        "poison_quarantined": h.get("chunks_quarantined") == 1,
        "checkpoint_healed": h.get("ckpt_fallback") is not None,
        "f_best_monotone": _trajectory_monotone(chaos.trace),
        "eps_within_tol": eps_chaos - eps_clean <= eps_tol,
    }
    return {
        "dataset": spec.name,
        "seed": seed,
        "eps_clean": eps_clean,
        "eps_chaos": eps_chaos,
        "eps_tol": eps_tol,
        "health": h,
        "transient_ids": plan.transient_ids(spec.n_chunks),
        "checks": checks,
        "ok": all(checks.values()),
    }


def main(argv=None) -> int:
    from repro.launch import compile_cache

    compile_cache.configure()
    from repro.evalsuite import datasets, gate

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--datasets", nargs="*", default=None,
                    help="registry names (default: the quick tier)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--eps-tol", type=float, default=gate.DEFAULT_EPS_TOL,
                    help="max eps_chaos - eps_clean (default: the suite "
                         "gate's epsilon tolerance)")
    ap.add_argument("--data-root", default=None)
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_chaos.json"))
    args = ap.parse_args(argv)

    names = args.datasets or datasets.list_datasets("quick")
    cells = []
    for name in names:
        spec = datasets.get_dataset(name)
        cell = run_cell(spec, seed=args.seed, data_root=args.data_root,
                        eps_tol=args.eps_tol)
        cells.append(cell)
        status = "ok" if cell["ok"] else "FAIL"
        print(f"{name:14s} eps_clean={cell['eps_clean']:+.4f}  "
              f"eps_chaos={cell['eps_chaos']:+.4f}  "
              f"quarantined={cell['health'].get('chunks_quarantined')}  "
              f"ckpt_fallback={cell['health'].get('ckpt_fallback')}  "
              f"[{status}]")
        for check, passed in cell["checks"].items():
            if not passed:
                print(f"  FAILED check: {check}")

    doc = {"bench": "chaos", "seed": args.seed, "cells": cells,
           "ok": all(c["ok"] for c in cells)}
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2, default=str)
    print(f"wrote {args.out}")
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
