"""Multi-device behaviour (8 forced host devices, separate process so the
main test process keeps its single-device view, per the launch spec)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.core import big_means, big_means_batched, big_means_sharded, full_objective
from repro.data.synthetic import GMMSpec, gmm_dataset
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
X = gmm_dataset(GMMSpec(m=16000, n=8, components=5, seed=2))
key = jax.random.PRNGKey(0)

out = {}
st, infos = big_means_sharded(
    X, key, mesh=mesh, k=5, s=800, chunks_per_worker=6, sync_every=2,
    axes=("data",))
out["f_sharded"] = float(full_objective(X, st.centroids)) / X.shape[0]
out["accepted"] = int(st.n_accepted)
out["n_infos"] = int(infos.f_new.shape[0])

# all-workers variant: every device is a worker
st2, _ = big_means_sharded(
    X, key, mesh=mesh, k=5, s=800, chunks_per_worker=4, sync_every=4,
    axes=("data", "model"))
out["f_allworkers"] = float(full_objective(X, st2.centroids)) / X.shape[0]

# sequential reference
st3, _ = big_means(X, key, k=5, s=800, n_chunks=24)
out["f_seq"] = float(full_objective(X, st3.centroids)) / X.shape[0]

# stream-mesh batched driver: sharding the stream axis over devices must
# reproduce the single-device batched result exactly (same key schedule)
smesh = make_mesh((4,), ("streams",))
stb, _ = big_means_batched(X, key, k=5, s=800, batch=8, rounds=3, impl="ref")
stm, _ = big_means_batched(X, key, k=5, s=800, batch=8, rounds=3, impl="ref",
                           mesh=smesh)
out["batched_mesh_matches"] = bool(
    np.allclose(float(stb.f_best), float(stm.f_best), rtol=1e-5)
    and np.allclose(np.asarray(stb.centroids), np.asarray(stm.centroids),
                    rtol=1e-4, atol=1e-4)
    and int(stb.n_accepted) == int(stm.n_accepted))

# chunk rows gathered from the packed copy or by rows: the same fits
from repro.engine import incore
X28 = gmm_dataset(GMMSpec(m=16000, n=28, components=5, seed=4))
def same(a, b):
    return all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
stream = [incore.batched_stream_mesh(
    X28, key, mesh=smesh, stream_axis="streams", k=5, s=800, batch=8,
    rounds=2, sync_every=1, max_iters=300, tol=1e-4, candidates=3,
    impl="ref", with_replacement=True, gather=g) for g in ("rows", "packed")]
out["stream_mesh_gathers_match"] = same(*stream)
workers = [incore.worker_sharded(
    X28, key, mesh=mesh, k=5, s=800, chunks_per_worker=4, sync_every=2,
    impl="ref", gather=g) for g in ("rows", "packed")]
out["worker_mesh_gathers_match"] = same(*workers)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def result():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_quality_matches_sequential(result):
    assert result["f_sharded"] <= result["f_seq"] * 1.15
    assert result["f_allworkers"] <= result["f_seq"] * 1.15


def test_sharded_progress(result):
    assert result["accepted"] >= 1
    # per-worker chunk traces concatenated over the 4 data-axis workers
    assert result["n_infos"] == 4 * 6


def test_batched_stream_mesh_matches_local(result):
    assert result["batched_mesh_matches"]


def test_a_stream_mesh_fit_is_the_same_from_either_gather(result):
    assert result["stream_mesh_gathers_match"]


def test_a_worker_mesh_fit_is_the_same_from_either_gather(result):
    assert result["worker_mesh_gathers_match"]
