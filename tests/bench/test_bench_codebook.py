"""The ``sift1m.codebook`` cell's pieces on the CPU: its row-blocked
float64 reference against the plain one, the fused kernel's roofline
share, and tiny runs of the cell whose ``correct`` holds, and fails when
the centroids are altered, the precision is lowered or the fit leaves the
fused kernel."""
import json

import jax
import numpy as np
import pytest

from bench import peaks, reference, reference_blocked, run, spec, trace, work
from repro.engine import incore
from repro.kernels import fused_step, ops

V5E = peaks.peaks("TPU v5 lite")
SIFT = "sift1m.codebook"


@pytest.fixture(autouse=True)
def fresh_programs():
    """Patched functions are only seen by programs traced after the patch."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _cell(k=32):
    """The cell at a CPU size: 20,000 rows, chunks of 1,000, k = 32 and,
    as in the tiny fit cells, one mixture component a centroid."""
    c = spec.load_cell(SIFT)
    c.config["dataset"]["m"] = 20_000
    c.config["dataset"]["components"] = k
    c.config["algorithm"]["s"] = 1_000
    c.config["algorithm"]["k"] = k
    return c


@pytest.mark.parametrize("rows", [7, 64, 5000])
def test_blocked_reference_equals_the_plain_one(rows):
    rng = np.random.default_rng(rows)
    x = rng.normal(0, 3, (300, 5)).astype(np.float32)
    c = rng.normal(0, 3, (11, 5)).astype(np.float32)
    c[3] += 100.0                               # a centroid with no rows
    assert reference_blocked.objective64(x, c, rows) == pytest.approx(
        reference.objective64(x, c), rel=1e-12)
    assert reference_blocked.lloyd_drop64(x, c, rows) == pytest.approx(
        reference.lloyd_drop64(x, c), rel=1e-12)


def _reduction(ops):
    return trace.Reduction(window_s=10.0, busy_s=[8.0], collective_s=[0.0],
                           ops=ops, idle_gaps=[])


def _read(ctx):
    return spec.metric_reader(spec.load_cell(SIFT), "fused_roofline.fit")(
        ctx)


def test_fused_roofline_is_the_kernel_ops_share():
    w = work.chunk_traffic(64000, 128, 4096, "f32", 160)
    ctx = {"reduction": _reduction([
        ("fused_step_batched_pallas.2 f32[8,32,128,128]", 1.5),
        ("fusion.7 f32[8,64000]", 3.0),
        ("fused_step_batched_pallas.5 f32[8,32,128,128]", 0.5)]),
        "counters": {"kernel_flops": w["flops"], "kernel_bytes": w["bytes"]},
        "peaks": V5E, "chips": 1}
    t_roof, bound = work.roofline_seconds(w["flops"], w["bytes"], V5E)
    assert bound == "flops"
    assert _read(ctx) == pytest.approx(100.0 * t_roof / 2.0)


def test_fused_roofline_reads_nothing_without_the_kernel_or_counters():
    counters = {"kernel_flops": 1e12, "kernel_bytes": 1e9}
    no_kernel = _reduction([("fusion.7 f32[8,64000]", 3.0)])
    assert _read({"reduction": no_kernel, "counters": counters,
                  "peaks": V5E, "chips": 1}) is None
    kernel = _reduction([("fused_step_batched_pallas.2 f32[8]", 1.0)])
    assert _read({"reduction": kernel, "counters": {"jobs": 3},
                  "peaks": V5E, "chips": 1}) is None
    assert _read({"reduction": None, "counters": counters,
                  "peaks": V5E, "chips": 1}) is None


def test_the_cell_is_declared_beside_the_fit_cells():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    (w,) = [w for w in bench["workloads"] if w["name"] == SIFT]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("sift1m", "fit_job.codebook", 1)
    cell = spec.load_cell(SIFT)
    assert [m["name"] for m in cell.end_to_end] == ["fit_job_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "idle_share.fit", "lloyd_roofline.fit", "fused_roofline.fit"]
    a = cell.config["algorithm"]
    assert (cell.config["dataset"]["m"], cell.config["dataset"]["n"],
            a["k"], a["s"], a["batch"]) == (1_000_000, 128, 4096, 64000, 8)


def test_a_tiny_codebook_run_is_correct_and_counts_its_work():
    cell = _cell()
    kind = spec.kind_module(cell).Kind(cell, 2**33 + 5)
    kind.setup(0.5)
    kind.window(0.5)
    c = kind.counters()
    kind.release()
    a = kind.algo
    want = [work.chunk_traffic(a["s"], 128, a["k"], "f32", j.n_iterations)
            for j in kind.jobs]
    assert c["kernel_flops"] == sum(x["flops"] for x in want)
    assert c["kernel_bytes"] == sum(x["bytes"] for x in want)
    assert c["kernel_flops"] < c["lloyd_flops"]     # no epilogue passes
    # every job's first round seeds all 8 streams
    assert c["seed_steps"] >= len(kind.jobs) * 8 * a["k"]
    assert c["seed_steps"] % a["k"] == 0
    checks = {n: (v, lim) for n, v, lim in kind.check()}
    assert all(v <= lim for v, lim in checks.values()), checks


def test_centroids_altered_where_they_are_produced_fail(monkeypatch):
    reduce_state = incore.reduce_state

    def nudged(states, *a, **kw):
        out = reduce_state(states, *a, **kw)
        return out._replace(centroids=out.centroids + 0.05)

    monkeypatch.setattr(incore, "reduce_state", nudged)
    r = run.run_cell(_cell(), 5, 0.5, False, platform="cpu")
    assert not r["correct"]
    assert r["checks"]["fit_obj_rel"]["value"] > 1e-4


def test_the_bf16_control_fails_the_codebook_cell():
    r = run.run_cell(_cell(), 5, 0.5, False, platform="cpu",
                     overrides={"precision": "bf16"})
    assert not r["correct"], r["checks"]


def test_a_fit_that_leaves_the_fused_kernel_stops_the_run(monkeypatch):
    """A program whose kernel envelope misses the cell's k runs its Lloyd
    steps on the two-pass path and says so in the fit's trace: the run
    reaches its end, reads the jobs' chunks past that event, and is not
    correct, by its kernel demotions alone."""
    monkeypatch.setattr(fused_step, "fits_batched", lambda k, n: False)
    ops.reset_kernel_demotions()
    ops.set_default_impl("pallas_interpret")
    try:
        r = run.run_cell(_cell(), 5, 0.5, False, platform="cpu")
    finally:
        ops.set_default_impl(None)
        ops.reset_kernel_demotions()
    assert not r["correct"]
    failed = [n for n, c in r["checks"].items() if c["value"] > c["limit"]]
    assert failed == ["kernel_demotions"], r["checks"]
