"""The benchmark's command and its cells, run on the CPU at tiny sizes.

The harness refuses the CPU; these tests call ``run.run_cell`` with
``platform="cpu"`` and drive everything else of a run."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import tiny
from bench import calibrate, run, spec, sweep
from repro.kernels import ops

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _command(cwd, tmp_path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "hepmass.fit",
         "--seed", "1", "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    return all('"correct"' not in line for line in stdout.splitlines())


def test_command_fails_without_a_tpu_and_prints_no_result(tmp_path):
    proc = _command(ROOT, tmp_path)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "nothing was run" in proc.stderr
    assert _no_result(proc.stdout)


def test_command_fails_without_the_program(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", alone)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, alone / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(alone, tmp_path)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert _no_result(proc.stdout)


def _keys_in_order(result):
    keys = list(result)
    assert keys[:3] == ["correct", "attempted", "failed"]
    assert keys[-1] == "checks"
    assert {"metrics", "device"} <= set(keys)


def test_fit_job_cell_runs_through_the_fused_kernel_and_is_correct():
    ops.set_default_impl("pallas_interpret")
    ops.reset_kernel_demotions()
    try:
        r = run.run_cell(tiny.cell("hepmass.fit"), 2**33 + 5, 0.5, False,
                         platform="cpu")
    finally:
        ops.set_default_impl(None)
    _keys_in_order(r)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"fit_job_s", "setup_s"}
    assert r["metrics"]["fit_job_s"]["unit"] == "s"
    assert r["checks"]["fit_obj_rel"]["value"] < 1e-5
    assert r["checks"]["fit_lloyd_drop"]["value"] < 1e-9
    assert r["checks"]["fit_accept_mismatch"]["value"] == 0


def test_open_loop_cell_answers_every_request_correctly():
    r = run.run_cell(tiny.cell("hepmass.serve"), 2**33 + 6, 1.0, False,
                     platform="cpu")
    _keys_in_order(r)
    assert r["correct"], r["checks"]
    assert r["attempted"] == tiny.SERVE["rate_per_s"] and r["failed"] == 0
    p50 = r["metrics"]["serve_p50_ms"]["value"]
    p95 = r["metrics"]["serve_p95_ms"]["value"]
    assert 0 < p50 <= p95 < 1000.0          # a tail, not the window length


def test_same_seed_same_inputs_and_schedule():
    c = tiny.cell("hepmass.serve")
    kind = spec.kind_module(c).Kind
    due1, sizes1 = kind(c, 7).schedule(2.0)
    due2, sizes2 = kind(c, 7).schedule(2.0)
    due3, sizes3 = kind(c, 8).schedule(2.0)
    assert (due1 == due2).all() and (sizes1 == sizes2).all()
    # another seed: the same multisets of gaps and sizes, in another order
    assert sorted(sizes1) == sorted(sizes3) and (sizes1 != sizes3).any()
    assert due1[-1] == pytest.approx(due3[-1]) == pytest.approx(2.0)
    assert 1 <= sizes1.min() and sizes1.max() <= 512


def test_the_stream_mesh_fit_is_correct_on_four_cpu_devices():
    """``hepmass.fit.mesh4`` on four virtual CPU devices, in a child with
    its own device count: the reference finds each incumbent's chunk in
    the mesh's trace order."""
    code = (
        "import sys, json; sys.path[:0] = [%r, %r]\n"
        "import tiny\nfrom bench import run\n"
        "r = run.run_cell(tiny.cell('hepmass.fit.mesh4'), 11, 0.5, False,"
        " platform='cpu')\nprint(json.dumps(r))\n"
        % (str(ROOT), str(pathlib.Path(__file__).parent)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"], r["checks"]


def test_calibrate_and_sweep_steps_run_on_the_cpu():
    c = tiny.cell("hepmass.fit")
    rec = calibrate.readings(c, 3, 0.3)
    assert rec["checks"]["fit_obj_rel"] < 1e-5 and rec["attempted"] >= 1
    c = tiny.cell("hepmass.serve")
    kind = spec.kind_module(c).Kind(c, 4)
    kind.setup(0.5)
    try:
        row = sweep.step(kind, 100.0, 0.5, 5)
    finally:
        kind.release()
    assert row["attempted"] == 50 and row["failed"] == 0 and row["correct"]
    assert row["p99_ms"] >= row["p50_ms"] > 0
