"""Parallel Big-means scaling (paper §2.2 properties 6-7, §3 parallelization).

Runs the sharded driver with 1/2/4/8 workers on forced host devices (its own
subprocess, so the main process keeps its device view), at a FIXED total
chunk budget: more workers process the budget in fewer rounds, and property
7 says quality should hold or improve (more independent incumbent streams =
more shaking).  Writes results/parallel_scaling.csv.

    PYTHONPATH=src python -m benchmarks.parallel_scaling
"""
from __future__ import annotations

import csv
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import jax
from repro.core import big_means_sharded, full_objective
from repro.data.synthetic import GMMSpec, gmm_dataset
from repro.launch.mesh import make_mesh

X = gmm_dataset(GMMSpec(m=64000, n=16, components=12, seed=6))
TOTAL_CHUNKS = 32
out = []
for w in (1, 2, 4, 8):
    mesh = make_mesh((w, 8 // w), ("data", "model"))
    for sync in (1, 4):
        cpw = TOTAL_CHUNKS // w
        if cpw % sync:
            continue
        t0 = time.monotonic()
        st, _ = big_means_sharded(
            X, jax.random.PRNGKey(0), mesh=mesh, k=12, s=2000,
            chunks_per_worker=cpw, sync_every=sync, axes=("data",))
        st.centroids.block_until_ready()
        wall = time.monotonic() - t0
        f = float(full_objective(X, st.centroids)) / X.shape[0]
        out.append({"workers": w, "sync_every": sync,
                    "chunks_per_worker": cpw, "f_per_point": f,
                    "wall_s": round(wall, 2)})
print("RESULT " + json.dumps(out))
"""


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.configure()
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    rows = json.loads(line[len("RESULT "):])
    path = os.path.join(REPO, "results", "parallel_scaling.csv")
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(rows[0]))
        w.writeheader()
        w.writerows(rows)
    for r in rows:
        print(f"workers={r['workers']} sync={r['sync_every']} "
              f"chunks/worker={r['chunks_per_worker']} "
              f"f/point={r['f_per_point']:.4f} wall={r['wall_s']}s")


if __name__ == "__main__":
    main()
