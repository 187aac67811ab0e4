"""``correct`` comes out false when the timed path is broken underneath,
and for the lower-precision control: tiny cells on the CPU, with every
part of a run but the harness's look for a chip."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from bench import faults, run
from repro.engine import incore
from repro.serve import registry


@pytest.fixture(autouse=True)
def fresh_programs():
    """Patched functions are only seen by programs traced after the patch."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _run(name, seed=5, seconds=0.5, rounds=None, **kw):
    cell = tiny.cell(name)
    if rounds:
        cell.mix["rounds"] = rounds
    return run.run_cell(cell, seed, seconds, False, platform="cpu", **kw)


def _limit(r, name):
    return r["checks"][name]["limit"]


def test_a_step_that_returns_its_state_unchanged_fails(monkeypatch):
    def unchanged(points, states, keys, **kw):
        b = states.f_best.shape[0]
        info = incore.ChunkInfo(
            f_new=jnp.full((b,), jnp.inf), accepted=jnp.zeros((b,), bool),
            lloyd_iters=jnp.zeros((b,), jnp.int32),
            n_degenerate=jnp.zeros((b,), jnp.int32))
        return states, info

    monkeypatch.setattr(incore, "chunk_step_batched", unchanged)
    with pytest.raises(RuntimeError, match="warm-up job"):
        _run("hepmass.fit")


def test_half_the_chunk_left_out_fails(monkeypatch):
    sample = incore.sample_chunk

    def half(X, key, s, **kw):
        x = sample(X, key, s // 2, **kw)
        return jnp.concatenate([x, x])        # the mean over the rest

    monkeypatch.setattr(incore, "sample_chunk", half)
    r = _run("hepmass.fit")
    assert not r["correct"]
    assert r["checks"]["fit_obj_rel"]["value"] > 1e-2


def test_centroids_altered_where_they_are_produced_fail(monkeypatch):
    reduce_state = incore.reduce_state

    def nudged(states, *a, **kw):
        out = reduce_state(states, *a, **kw)
        return out._replace(centroids=out.centroids + 0.05)

    monkeypatch.setattr(incore, "reduce_state", nudged)
    r = _run("hepmass.fit")
    assert not r["correct"]
    assert r["checks"]["fit_obj_rel"]["value"] > 1e-4


def test_a_kernel_that_leaves_the_centroids_unchanged_fails():
    """The fused kernel's sums leave every centroid where it was: the job
    returns its K-means++ seeds, whose objective it still reports right,
    so only one float64 Lloyd step from them tells."""
    with faults.planted("kernel_unchanged"):
        r = _run("hepmass.fit")
    assert not r["correct"]
    assert r["checks"]["fit_lloyd_drop"]["value"] > 1e-2
    assert r["checks"]["fit_obj_rel"]["value"] <= _limit(r, "fit_obj_rel")


def test_a_kernel_that_sums_half_the_rows_fails():
    """Each centroid is the mean over the first half of its rows."""
    with faults.planted("kernel_half_rows"):
        r = _run("hepmass.fit")
    assert not r["correct"]
    assert r["checks"]["fit_lloyd_drop"]["value"] > \
        100 * _limit(r, "fit_lloyd_drop")
    assert r["checks"]["fit_obj_rel"]["value"] <= _limit(r, "fit_obj_rel")


def test_the_periodic_sync_left_out_fails():
    """The streams of a chip never exchange incumbents before the end:
    the final reduction still returns the best chunk's, but chunks are
    accepted that keep-the-best over the fleet would refuse."""
    with faults.planted("no_periodic_sync"):
        r = _run("hepmass.fit", rounds=6)
    assert not r["correct"]
    assert r["checks"]["fit_accept_mismatch"]["value"] > 0
    assert r["checks"]["fit_obj_rel"]["value"] <= _limit(r, "fit_obj_rel")


def _mesh4(preamble: str, rounds: int = 2) -> dict:
    """``hepmass.fit.mesh4`` on four virtual CPU devices, in a child with
    its own device count, after ``preamble``."""
    here = pathlib.Path(__file__).parent
    code = (
        "import sys, json; sys.path[:0] = [%r, %r]\n"
        "import jax, jax.numpy as jnp\n"
        "import tiny\nfrom bench import faults, run\n"
        "c = tiny.cell('hepmass.fit.mesh4'); c.mix['rounds'] = %d\n"
        "%s\n"
        "print(json.dumps(r))\n"
        % (str(here.parents[1]), str(here), rounds, preamble))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(here.parents[1] / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_periodic_exchange_between_chips_left_out_fails():
    """Four virtual devices; the incumbent exchange every ``sync_every``
    rounds is left out, the final one kept."""
    r = _mesh4("with faults.planted('no_periodic_sync'):\n"
               "    r = run.run_cell(c, 11, 1.0, False, platform='cpu')",
               rounds=6)
    assert not r["correct"], r["checks"]
    assert r["checks"]["fit_accept_mismatch"]["value"] > 0


def test_the_exchange_between_chips_left_out_fails():
    """Four virtual CPU devices; every all-gather sees only its own chip's
    value, so the job returns chip 0's incumbent, not the fleet's."""
    r = _mesh4("jax.lax.all_gather = lambda x, axis, **kw: "
               "jnp.broadcast_to(x, (4,) + jnp.shape(x))\n"
               "r = run.run_cell(c, 11, 1.0, False, platform='cpu')")
    assert not r["correct"], r["checks"]


def test_an_answer_altered_where_it_is_produced_fails(monkeypatch):
    launch = registry.ModelEntry.launch

    def flipped(self, q, snapshot):
        ids, d = launch(self, q, snapshot)
        ids = ids.copy()
        ids[0] = (ids[0] + 1) % snapshot.k
        return ids, d

    monkeypatch.setattr(registry.ModelEntry, "launch", flipped)
    r = _run("hepmass.serve", seconds=1.0)
    assert not r["correct"]
    assert r["checks"]["serve_id_gap"]["value"] > 1e-3


def test_half_the_batch_left_out_fails(monkeypatch):
    launch = registry.ModelEntry.launch

    def half(self, q, snapshot):
        ids, d = launch(self, q, snapshot)
        rows = max(1, int(np.count_nonzero(np.asarray(q).any(1))) // 2)
        return ids[:rows], d[:rows]

    monkeypatch.setattr(registry.ModelEntry, "launch", half)
    r = _run("hepmass.serve", seconds=1.0)
    assert not r["correct"]
    assert r["checks"]["unanswered_or_malformed"]["value"] > 0
    assert r["failed"] > 0


@pytest.mark.parametrize("name", ["hepmass.fit", "cord19.fit",
                                  "hepmass.serve"])
def test_the_bf16_control_fails(name):
    """The program's bf16 path in the configuration's place fails every
    cell at this size on the CPU.  (The bf16x3 control fails on the chip;
    on the CPU its three products are exact enough to pass.)"""
    r = _run(name, seconds=1.0, overrides={"precision": "bf16"})
    assert not r["correct"], r["checks"]
