"""Benchmark harness — one section per paper table/figure.

  table5_50   per-dataset quality/time/n_d summaries (paper Tables 5-50)
  table3_4    normalized score summary across datasets (paper Tables 3-4)
  fig1_4      distance-evaluation counts vs k (paper Figures 1-4)
  chunk_sweep chunk-size trade-off (paper §4.1 analysis)
  kernels     per-kernel microbenchmarks (us/call)

Run everything: ``PYTHONPATH=src python -m benchmarks.run``
Subset:         ``... -m benchmarks.run --only tables --fast``
Prints ``name,us_per_call,derived`` CSV rows; writes detailed CSVs to
results/.
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import time

import jax
import numpy as np

from benchmarks import common

RESULTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")

_ROWS: list = []        # every _emit row, for the --smoke JSON artifact


def _emit(name, us, derived):
    print(f"{name},{us:.1f},{derived}")
    _ROWS.append({"name": name, "us_per_call": round(us, 1),
                  "derived": derived})


def bench_tables(rows, outdir):
    err = common.relative_errors(rows)
    path = os.path.join(outdir, "table5_50_quality.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["dataset", "k", "algo", "EA_min", "EA_mean", "EA_max",
                    "cpu_s", "n_d"])
        for (ds, k, algo), v in sorted(err.items()):
            w.writerow([ds, k, algo, f"{v['min']:.3f}", f"{v['mean']:.3f}",
                        f"{v['max']:.3f}", f"{v['cpu']:.3f}",
                        f"{v['n_d']:.3e}"])
    for (ds, k, algo), v in sorted(err.items()):
        if algo == "bigmeans":
            _emit(f"table5_50/{ds}/k{k}/bigmeans",
                  v["cpu"] * 1e6, f"EA_mean={v['mean']:.3f}%")
    sc = common.scores(rows)
    path = os.path.join(outdir, "table3_4_scores.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["algo", "accuracy_score", "cpu_score", "max_possible"])
        for a in sorted(sc["accuracy"]):
            w.writerow([a, f"{sc['accuracy'][a]:.3f}", f"{sc['cpu'][a]:.3f}",
                        sc["n_datasets"]])
    nds = sc["n_datasets"]
    for a in sorted(sc["accuracy"]):
        _emit(f"table3_4/{a}", 0.0,
              f"acc={sc['accuracy'][a]:.2f}/{nds};cpu={sc['cpu'][a]:.2f}/{nds}")
    # figures 1-4: n_d vs k per algo
    path = os.path.join(outdir, "fig1_4_distance_evals.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["dataset", "k", "algo", "n_d"])
        for (ds, k, algo), v in sorted(err.items()):
            w.writerow([ds, k, algo, f"{v['n_d']:.3e}"])
    return sc


def bench_chunk_sweep(outdir, fast=False):
    """Paper §4.1: chunk size controls approximation/variability balance."""
    from repro.core import big_means, full_objective
    from repro.data.synthetic import GMMSpec, gmm_dataset
    X = gmm_dataset(GMMSpec(m=40000, n=20, components=15, spread=4.0, seed=4))
    sizes = (250, 1000, 4000) if fast else (125, 250, 500, 1000, 2000, 4000,
                                            8000)
    path = os.path.join(outdir, "chunk_size_sweep.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["s", "f_mean", "f_std", "cpu_s"])
        for s in sizes:
            fs, t0 = [], time.monotonic()
            for e in range(3):
                st, _ = big_means(X, jax.random.PRNGKey(e), k=15, s=s,
                                  n_chunks=30)
                fs.append(float(full_objective(X, st.centroids)))
            cpu = (time.monotonic() - t0) / 3
            w.writerow([s, f"{np.mean(fs):.4e}", f"{np.std(fs):.4e}",
                        f"{cpu:.3f}"])
            _emit(f"chunk_sweep/s{s}", cpu * 1e6,
                  f"f_mean={np.mean(fs):.4e}")


def bench_kernels(outdir):
    """us/call for the hot kernels (jnp reference path on CPU; the Pallas
    kernels target TPU and are validated in interpret mode by tests)."""
    from repro.kernels import ops
    shapes = [(16384, 64, 25), (65536, 28, 25), (8192, 512, 25)]
    path = os.path.join(outdir, "kernel_bench.csv")
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["kernel", "m", "n", "k", "us_per_call", "gflops"])
        for m, n, k in shapes:
            x = jax.random.normal(jax.random.PRNGKey(0), (m, n))
            c = jax.random.normal(jax.random.PRNGKey(1), (k, n))
            ids, _ = ops.assign(x, c, impl="ref")
            for name, fn in (
                ("assign", lambda: ops.assign(x, c, impl="ref")[1]),
                ("update", lambda: ops.update(x, ids, k, impl="ref")[0]),
            ):
                fn().block_until_ready()
                t0 = time.monotonic()
                reps = 5
                for _ in range(reps):
                    fn().block_until_ready()
                us = (time.monotonic() - t0) / reps * 1e6
                flops = 2.0 * m * n * k if name == "assign" else 2.0 * m * n
                w.writerow([name, m, n, k, f"{us:.1f}",
                            f"{flops / (us * 1e-6) / 1e9:.2f}"])
                _emit(f"kernel/{name}/m{m}n{n}k{k}", us,
                      f"gflops={flops / (us * 1e-6) / 1e9:.2f}")


def bench_smoke(outdir):
    """CI smoke run: kernel microbenchmarks + a minimal batched-throughput
    probe, written to results/BENCH_smoke.json (uploaded as a CI artifact)."""
    from repro.core import big_means_batched
    from repro.data.synthetic import GMMSpec, gmm_dataset

    bench_kernels(outdir)
    X = gmm_dataset(GMMSpec(m=40000, n=20, components=15, seed=4))
    for batch in (1, 4):
        rounds = 8 // batch
        fn = lambda: big_means_batched(
            X, jax.random.PRNGKey(0), k=25, s=4096, batch=batch,
            rounds=rounds, impl="ref")[0].f_best.block_until_ready()
        fn()                                   # compile
        t0 = time.monotonic()
        fn()
        dt = time.monotonic() - t0
        _emit(f"smoke/batched/b{batch}", dt * 1e6 / 8,
              f"chunks_per_s={8 / dt:.2f}")
    path = os.path.join(outdir, "BENCH_smoke.json")
    with open(path, "w") as f:
        json.dump(_ROWS, f, indent=1)
    print(f"# wrote {path}")


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.configure()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=["tables", "chunk_sweep", "kernels"])
    ap.add_argument("--fast", action="store_true",
                    help="reduced suite for smoke runs")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke subset; writes results/BENCH_smoke.json")
    args = ap.parse_args()
    os.makedirs(RESULTS, exist_ok=True)

    if args.smoke:
        bench_smoke(RESULTS)
        return
    if args.only in (None, "kernels"):
        bench_kernels(RESULTS)
    if args.only in (None, "chunk_sweep"):
        bench_chunk_sweep(RESULTS, fast=args.fast)
    if args.only in (None, "tables"):
        suite = common.SUITE[:3] if args.fast else common.SUITE
        kv = (2, 10) if args.fast else common.K_VALUES
        ne = 1 if args.fast else common.N_EXEC
        rows = common.full_sweep(suite=suite, k_values=kv, n_exec=ne)
        sc = bench_tables(rows, RESULTS)
        print("# scores:", {k: round(v, 2) for k, v in sc["accuracy"].items()})


if __name__ == "__main__":
    main()
