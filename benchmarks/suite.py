"""CLI for the §5 reproduction suite (`repro.evalsuite`).

Runs the Big-means-vs-baselines quality/speed sweep over the dataset
registry and writes one schema-validated ``BENCH_suite.json`` (repo root)
plus ``results/suite_runs.csv``.  The regression gate diffs that artifact
against the committed ``results/BENCH_baseline.json``:

    PYTHONPATH=src python -m benchmarks.suite --quick
    PYTHONPATH=src python -m repro.evalsuite.gate \
        --baseline results/BENCH_baseline.json --fresh BENCH_suite.json

Refreshing the committed baseline after an intentional quality change:

    PYTHONPATH=src python -m benchmarks.suite --quick \
        --out results/BENCH_baseline.json
"""
from __future__ import annotations

import argparse
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    from repro.launch import compile_cache

    compile_cache.configure()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="the PR-gate tier: small datasets, 2 seeds "
                         "(default: the full nightly tier)")
    ap.add_argument("--seeds", type=int, default=None, metavar="N",
                    help="override the number of seeds (0..N-1)")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_suite.json"))
    ap.add_argument("--csv",
                    default=os.path.join(REPO, "results", "suite_runs.csv"))
    ap.add_argument("--data-root", default=None,
                    help="where dataset memmaps materialize "
                         "(default: a per-user temp dir)")
    args = ap.parse_args(argv)

    from repro.evalsuite import suite

    tier = "quick" if args.quick else "full"
    seeds = tuple(range(args.seeds)) if args.seeds is not None else None
    doc = suite.run_suite(tier, seeds=seeds, data_root=args.data_root)
    suite.write_outputs(doc, args.out, args.csv)

    for cell in doc["cells"]:
        print(f"{cell['dataset']:14s} {cell['method']:22s} "
              f"eps_mean={cell['epsilon_mean']:+.4f}  "
              f"success={cell['success_rate']:.2f}  "
              f"wall={cell['wall_mean_s']:6.2f}s")
    print(f"wrote {args.out} and {args.csv}")
    bootstrap = [d["name"] for d in doc["datasets"]
                 if d.get("f_star_source") != "committed"]
    if bootstrap:
        print("NOTE: uncommitted f_star (run-best bootstrap) for: "
              + ", ".join(bootstrap))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
