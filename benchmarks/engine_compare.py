"""Engine composition benchmark: fixed-s vs competitive-s at equal budget.

The ``competitive_s`` scheduler (arXiv:2403.18766) races per-stream sample
sizes and reallocates streams toward the empirically winning ``s``.  This
benchmark gives every contender the SAME total chunk budget and compares
the full-data objective f(C, X):

* ``fixed_s`` rows — the uniform scheduler at each ladder size alone (what
  you get when you hand-pick that ``s``);
* ``competitive_s`` row — the racing scheduler over the whole ladder, plus
  which size won (its surviving allocation).

The point is robustness, not a guaranteed win: a hand-picked *good* ``s``
ties the race, but a hand-picked *bad* one loses to it — and the race never
needed the pick.  All runs go through ``repro.api.fit`` on the streaming
strategy (the engine's persistent-stream loop), ``impl='ref'``.

Writes BENCH_engine.json at the repo root (committed — the quality
trajectory future PRs regress against) and results/engine_compare.csv.

    PYTHONPATH=src python -m benchmarks.engine_compare [--fast]
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(fast: bool = False, topology: str = "single"):
    from repro.launch import compile_cache

    compile_cache.configure()
    from repro.api import BigMeansConfig, evaluate, fit
    from repro.data.synthetic import GMMSpec, gmm_dataset

    m = 20000 if fast else 40000
    k, n = 15, 20
    ladder = (256, 4096, 16384) if not fast else (128, 2048, 8192)
    s_mid = ladder[1]
    batch = 6
    n_chunks = 48 if fast else 96
    X = gmm_dataset(GMMSpec(m=m, n=n, components=k, spread=4.0, seed=11))

    rows = []

    def run(name, cfg):
        t0 = time.monotonic()
        r = fit(X, cfg, method="streaming")
        wall = time.monotonic() - t0
        _, f_full = evaluate(r, X)
        row = {
            "variant": name,
            "scheduler": cfg.scheduler,
            "s": cfg.s,
            "batch": cfg.batch,
            "n_chunks": n_chunks,
            "chunks_done": r.n_chunks,
            "f_full_per_point": round(f_full / m, 6),
            "n_accepted": r.n_accepted,
            "lloyd_iters": r.n_iterations,
            "wall_s": round(wall, 3),
        }
        if "competitive_s" in r.extras:
            info = r.extras["competitive_s"]
            row["ladder"] = list(info["ladder"])
            row["final_sizes"] = info["final_sizes"]
            row["windows"] = info["windows"]
        rows.append(row)
        print(f"{name:>22}: f/point={row['f_full_per_point']:.4f}  "
              f"chunks={r.n_chunks}  wall={wall:.2f}s")
        return row

    # fixed-s contenders: each ladder size alone, equal chunk budget
    for s in ladder:
        cfg = BigMeansConfig(k=k, s=s, n_chunks=n_chunks, batch=batch,
                             sync_every=2, impl="ref", seed=3,
                             log_every=0, topology=topology)
        run(f"fixed_s={s}", cfg)

    # the race over the same ladder, same budget
    cfg = BigMeansConfig(k=k, s=s_mid, n_chunks=n_chunks, batch=batch,
                         sync_every=2, scheduler="competitive_s",
                         competitive_ladder=ladder, impl="ref", seed=3,
                         log_every=0, topology=topology)
    run("competitive_s", cfg)

    best_fixed = min(r["f_full_per_point"] for r in rows[:-1])
    worst_fixed = max(r["f_full_per_point"] for r in rows[:-1])
    comp = rows[-1]["f_full_per_point"]
    summary = {
        "best_fixed_f_per_point": best_fixed,
        "worst_fixed_f_per_point": worst_fixed,
        "competitive_f_per_point": comp,
        "competitive_vs_best_fixed": round(comp / best_fixed, 4),
        "competitive_vs_worst_fixed": round(comp / worst_fixed, 4),
    }
    from repro.evalsuite import schema as bench_schema

    out = bench_schema.envelope(
        "engine_compare", rows,
        dataset={"m": m, "n": n, "components": k},
        k=k,
        ladder=list(ladder),
        equal_chunk_budget=n_chunks,
        impl="ref",
        summary=summary,
    )
    path = bench_schema.write_bench(
        os.path.join(REPO, "BENCH_engine.json"), out)
    os.makedirs(os.path.join(REPO, "results"), exist_ok=True)
    csv_path = os.path.join(REPO, "results", "engine_compare.csv")
    keys = ["variant", "scheduler", "s", "batch", "n_chunks", "chunks_done",
            "f_full_per_point", "n_accepted", "lloyd_iters", "wall_s"]
    with open(csv_path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(keys)
        for r in rows:
            w.writerow([r.get(c, "") for c in keys])
    print(f"summary: {json.dumps(summary)}")
    print(f"wrote {path} and {csv_path}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller dataset / budget (CI smoke)")
    ap.add_argument("--topology", default="single",
                    choices=["single", "stream_mesh", "host_mesh", "auto"],
                    help="declarative execution placement (BigMeansConfig"
                         ".topology); host_mesh expects the REPRO_* "
                         "bootstrap env vars")
    args = ap.parse_args()
    main(fast=args.fast, topology=args.topology)
