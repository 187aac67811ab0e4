"""Multi-host streaming: the N=2 process single-machine suite.

Real two-process runs go through :func:`repro.engine.hostmesh.launch_local`
(fresh coordinator port, ``REPRO_*`` bootstrap env); each rank is a small
script that prints a ``RESULT`` JSON line and exits via ``os._exit`` so the
``jax.distributed`` atexit shutdown cannot turn an intentionally-killed-peer
test into a spurious abort.  Exchanger mechanics (shard math, argmin
tie-break, counter deltas, straggler/dead events) are unit-tested in-process
against a fake runtime.
"""
import json
import os
import sys
import types

import numpy as np
import pytest

from repro.api import BigMeansConfig
from repro.engine import hostmesh
from repro.engine.faults import HostDead
from repro.engine.stream import RunnerMetrics
from repro.engine.topology import HostMesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = BigMeansConfig(k=4, s=64, n_chunks=8, batch=4, log_every=0,
                     impl="ref", prefetch=2)


def _provider(cid):
    """Pure in chunk id — every process regenerates identical chunks."""
    rng = np.random.default_rng((11, cid))
    return rng.normal(size=(64, 5)).astype(np.float32)


# ---------------------------------------------------------------------------
# exchanger unit tests (fake runtime, no jax.distributed)
# ---------------------------------------------------------------------------


class _FakeRuntime:
    def __init__(self, processes=2, rank=0, gathered=None, raise_dead=False):
        self.processes = processes
        self.rank = rank
        self._gathered = gathered
        self._raise = raise_dead

    def allgather(self, tag, payload):
        if self._raise:
            raise HostDead("rank 1 missed exchange", rank=self.rank)
        return self._gathered


def _ctx():
    return types.SimpleNamespace(metrics=RunnerMetrics(), last_s=64,
                                 extras={})


def test_chunk_id_sharding_preserves_global_window_order():
    ex0 = hostmesh.HostExchanger(_FakeRuntime(2, 0), CFG)
    ex1 = hostmesh.HostExchanger(_FakeRuntime(2, 1), CFG)
    assert list(ex0.chunk_ids(0)) == [0, 1, 4, 5]
    assert list(ex1.chunk_ids(0)) == [2, 3, 6, 7]
    # the union per window is contiguous: window w covers w*B..w*B+B-1
    assert sorted(list(ex0.chunk_ids(0)) + list(ex1.chunk_ids(0))) == \
        list(range(8))
    # resume from a window frontier drops exactly the finished windows
    assert list(ex0.chunk_ids(4)) == [4, 5]
    assert list(ex1.chunk_ids(4)) == [6, 7]


def test_winner_argmin_breaks_ties_toward_lowest_rank():
    g = [{"f": np.float32(8.0), "size": np.int64(64)},
         {"f": np.float32(8.0), "size": np.int64(64)}]
    assert hostmesh.HostExchanger._winner(g) == 0
    g[1]["f"] = np.float32(7.0)
    assert hostmesh.HostExchanger._winner(g) == 1
    # per-point comparison: a smaller raw f on a smaller chunk can lose
    g = [{"f": np.float32(10.0), "size": np.int64(100)},
         {"f": np.float32(6.0), "size": np.int64(50)}]
    assert hostmesh.HostExchanger._winner(g) == 0


def test_counter_delta_merge_is_exactly_once():
    ex = hostmesh.HostExchanger(_FakeRuntime(2, 0), CFG)
    ex._counters = (10, 100.0)
    acc, nd = ex._merge_counters([
        {"acc": np.int64(14), "nd": np.float64(130.0)},
        {"acc": np.int64(13), "nd": np.float64(120.0)},
    ])
    assert (acc, nd) == (17, 150.0)
    assert ex._counters == (17, 150.0)


def test_straggler_gather_is_traced():
    ticks = iter([0.0, 9.0])
    ex = hostmesh.HostExchanger(
        _FakeRuntime(2, 0, gathered=[{}, {}]), CFG,
        straggler_s=5.0, clock=lambda: next(ticks))
    ctx = _ctx()
    ex._gather(ctx, "x0", {}, 0)
    assert ("host_straggler", 0, 9.0) in ctx.metrics.trace


def test_dead_peer_enriches_typed_fault():
    ex = hostmesh.HostExchanger(_FakeRuntime(2, 0, raise_dead=True), CFG)
    ctx = _ctx()
    ctx.metrics.chunks_done = 2
    with pytest.raises(HostDead) as ei:
        ex._gather(ctx, "x3", {}, 3)
    assert ei.value.window == 3
    assert ei.value.health["chunks_done"] == 2
    assert ei.value.health["chunks_fetched"] == 2
    assert any(t[0] == "host_dead" and t[1] == 3
               for t in ctx.metrics.trace)


def test_run_host_stream_validates_composition():
    topo2 = HostMesh(processes=2, rank=0)
    with pytest.raises(ValueError, match="divide the global batch"):
        hostmesh.run_host_stream(_provider, CFG.replace(batch=3, n_chunks=9),
                                 topology=topo2, n_features=5)
    with pytest.raises(ValueError, match="divide n_chunks"):
        hostmesh.run_host_stream(_provider, CFG.replace(n_chunks=10),
                                 topology=topo2, n_features=5)
    with pytest.raises(ValueError, match="vns_ladder"):
        hostmesh.run_host_stream(_provider, CFG.replace(vns_ladder=(64,)),
                                 topology=topo2, n_features=5)
    with pytest.raises(ValueError, match="time_budget_s"):
        hostmesh.run_host_stream(_provider, CFG.replace(time_budget_s=5.0),
                                 topology=topo2, n_features=5)
    with pytest.raises(ValueError, match="competitive_s"):
        hostmesh.run_host_stream(
            _provider,
            CFG.replace(batch=2, scheduler="competitive_s", sync_every=4,
                        n_chunks=8),
            topology=topo2, n_features=5)


def test_launch_local_env_contract():
    script = ("import os, json; "
              "print('RESULT ' + json.dumps({"
              "'rank': os.environ['REPRO_HOST_RANK'], "
              "'hosts': os.environ['REPRO_NUM_HOSTS'], "
              "'coord': os.environ['REPRO_COORD']}))")
    procs = hostmesh.launch_local([sys.executable, "-c", script], 2,
                                  timeout_s=60)
    assert [p.returncode for p in procs] == [0, 0]
    outs = [json.loads(p.output.splitlines()[-1][len("RESULT "):])
            for p in procs]
    assert [o["rank"] for o in outs] == ["0", "1"]
    assert outs[0]["hosts"] == "2"
    assert outs[0]["coord"] == outs[1]["coord"]
    assert outs[0]["coord"].startswith("127.0.0.1:")


def test_launch_local_children_inherit_platform(monkeypatch):
    """No platform is forced on the children: they see the parent's."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    script = ("import os; print('RESULT ' + "
              "os.environ.get('JAX_PLATFORMS', '<unset>'))")
    procs = hostmesh.launch_local([sys.executable, "-c", script], 1,
                                  timeout_s=60)
    assert procs[0].output.splitlines()[-1] == "RESULT cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    procs = hostmesh.launch_local([sys.executable, "-c", script], 1,
                                  timeout_s=60)
    assert procs[0].output.splitlines()[-1] == "RESULT <unset>"


def test_launch_local_refuses_when_parent_holds_accelerator(monkeypatch):
    import jax

    jax.devices()                       # the CPU backend, initialized
    assert not hostmesh.holds_accelerator()
    monkeypatch.setattr(hostmesh, "holds_accelerator", lambda: True)
    with pytest.raises(RuntimeError, match="holds its chips"):
        hostmesh.launch_local([sys.executable, "-c", "pass"], 2)


# ---------------------------------------------------------------------------
# real 2-process runs
# ---------------------------------------------------------------------------

_RANK_SCRIPT = r"""
import os, json
import numpy as np

import jax
from repro.api import BigMeansConfig, TopologySpec, fit

def provider(cid):
    rng = np.random.default_rng((11, cid))
    return rng.normal(size=(64, 5)).astype(np.float32)

spec = TopologySpec(kind="host_mesh", sync_timeout_s=20.0)
base = dict(k=4, s=64, n_chunks=8, batch=4, log_every=0, impl="ref",
            prefetch=2, topology=spec)

out = {}
# fold mode: collective sync (sync_every=1)
r = fit(provider, BigMeansConfig(**base), method="streaming", n_features=5)
out["fold"] = {
    "f": float(r.objective),
    "C": np.asarray(r.centroids).tolist(),
    "accepted": int(r.n_accepted),
    "host": r.extras["host"],
    "ranks": r.extras["health"]["ranks"],
    "host_sync_windows": [t[1] for t in r.trace if t[0] == "host_sync"],
}
# persistent mode: periodic sync (sync_every=2 over 2 local streams)
r2 = fit(provider, BigMeansConfig(**dict(base, sync_every=2)),
         method="streaming", n_features=5)
out["persistent"] = {
    "f": float(r2.objective),
    "C": np.asarray(r2.centroids).tolist(),
    "accepted": int(r2.n_accepted),
}
print("RESULT " + json.dumps(out), flush=True)
os._exit(0)   # skip the jax.distributed atexit teardown race
"""

_KILLED_SCRIPT = r"""
import os, json
import numpy as np

import jax
from repro.api import BigMeansConfig, TopologySpec, fit
from repro.engine.faults import HostDead

rank = int(os.environ["REPRO_HOST_RANK"])

def provider(cid):
    # rank 1 dies on its first own chunk (after the collective start), so
    # rank 0 completes its window-0 chunks and then times out at the
    # exchange -- exercising the typed-fault path with non-zero accounting
    if rank == 1 and cid in (2, 3):
        os._exit(3)
    rng = np.random.default_rng((11, cid))
    return rng.normal(size=(64, 5)).astype(np.float32)

spec = TopologySpec(kind="host_mesh", sync_timeout_s=8.0)
cfg = BigMeansConfig(k=4, s=64, n_chunks=8, batch=4, log_every=0,
                     impl="ref", prefetch=0, topology=spec)
try:
    fit(provider, cfg, method="streaming", n_features=5)
    out = {"host_dead": False}
except HostDead as e:
    out = {"host_dead": True, "rank": e.rank, "window": e.window,
           "health": e.health}
print("RESULT " + json.dumps(out), flush=True)
os._exit(0)   # the surviving rank must report cleanly, not abort at exit
"""


def _parse(proc):
    lines = [l for l in proc.output.splitlines() if l.startswith("RESULT ")]
    assert lines, (proc.rank, proc.returncode, proc.output[-3000:])
    return json.loads(lines[-1][len("RESULT "):])


@pytest.fixture(scope="module")
def two_proc():
    env = {"PYTHONPATH": os.path.join(REPO, "src")}
    procs = hostmesh.launch_local(
        [sys.executable, "-c", _RANK_SCRIPT], 2, timeout_s=540,
        env_extra=env)
    for p in procs:
        assert p.returncode == 0, (p.rank, p.output[-3000:])
    return [_parse(p) for p in procs]


@pytest.fixture(scope="module")
def reference():
    """The single-process runs at the same global chunk budget."""
    from repro.api import fit

    outs = {}
    r = fit(_provider, CFG, method="streaming", n_features=5)
    outs["fold"] = r
    r2 = fit(_provider, CFG.replace(sync_every=2), method="streaming",
             n_features=5)
    outs["persistent"] = r2
    return outs


def test_two_process_fold_bit_identical_to_single(two_proc, reference):
    ref = reference["fold"]
    for rank_out in two_proc:
        assert rank_out["fold"]["f"] == float(ref.objective)
        np.testing.assert_array_equal(
            np.asarray(rank_out["fold"]["C"], dtype=np.float32),
            np.asarray(ref.centroids))
        assert rank_out["fold"]["accepted"] == int(ref.n_accepted)


def test_two_process_persistent_bit_identical_to_single(two_proc, reference):
    ref = reference["persistent"]
    for rank_out in two_proc:
        assert rank_out["persistent"]["f"] == float(ref.objective)
        np.testing.assert_array_equal(
            np.asarray(rank_out["persistent"]["C"], dtype=np.float32),
            np.asarray(ref.centroids))
        assert rank_out["persistent"]["accepted"] == int(ref.n_accepted)


def test_two_process_health_and_sync_events(two_proc):
    for rank, out in enumerate(two_proc):
        assert out["fold"]["host"]["processes"] == 2
        assert out["fold"]["host"]["rank"] == rank
        ranks = out["fold"]["ranks"]
        assert [h["rank"] for h in ranks] == [0, 1]
        for h in ranks:
            assert h["chunks_done"] == 4            # 8 chunks over 2 ranks
            assert (h["chunks_done"] + h["chunks_failed"]
                    + h["chunks_dropped"] + h["chunks_quarantined"]
                    == h["chunks_fetched"])
        # collective sync: an exchange per window plus the final reduce
        assert out["fold"]["host_sync_windows"] == [0, 1, "final"]


def test_killed_process_fails_fast_with_typed_fault():
    env = {"PYTHONPATH": os.path.join(REPO, "src")}
    procs = hostmesh.launch_local(
        [sys.executable, "-c", _KILLED_SCRIPT], 2, timeout_s=540,
        env_extra=env)
    dead = procs[1]
    assert dead.returncode == 3                 # rank 1 killed itself
    survivor = _parse(procs[0])
    assert survivor["host_dead"] is True
    assert survivor["rank"] == 0
    assert survivor["window"] == 0              # the first exchange window
    h = survivor["health"]
    assert h["chunks_done"] == 2                # rank 0's window-0 chunks
    assert (h["chunks_done"] + h["chunks_failed"] + h["chunks_dropped"]
            + h["chunks_quarantined"]) == h["chunks_fetched"]
