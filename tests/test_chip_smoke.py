"""``chip_smoke.py`` off the chip: it refuses to run without a TPU or a
checkout, and its phases run end to end on the CPU at small sizes with the
Pallas kernels in interpret mode (the checks are the chip run's own)."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.kernels import ops

REPO = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = REPO / "chip_smoke.py"
SMALL = dict(m=40_000, s=1_000, rounds=2, stream_chunks=8, sample_every=10,
             requests=12, request_sizes=(1, 7, 64, 100, 300))


def _run_script(cwd, script, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _no_contract_line(stdout: str) -> bool:
    return all('"ok"' not in line for line in stdout.splitlines())


def test_exits_nonzero_without_a_tpu(tmp_path):
    proc = _run_script(REPO, SCRIPT, tmp_path)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    assert _no_contract_line(proc.stdout)


def test_exits_nonzero_outside_a_checkout(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone / SCRIPT.name)
    proc = _run_script(alone, alone / SCRIPT.name, tmp_path)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no repro package" in proc.stderr
    assert _no_contract_line(proc.stdout)


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, "chip_smoke", mod)    # for dataclasses
    spec.loader.exec_module(mod)
    ops.set_default_impl("pallas_interpret")
    ops.reset_kernel_demotions()
    yield mod
    ops.set_default_impl(None)
    ops.reset_kernel_demotions()


def test_one_chip_phases_pass_on_cpu_in_interpret_mode(smoke, monkeypatch,
                                                       capsys):
    from repro.configs import bigmeans_paper

    sz = smoke.Sizes(**SMALL)
    # The streaming phase runs launch/train.py, which reads the paper config.
    monkeypatch.setattr(bigmeans_paper.CONFIG, "algo",
                        bigmeans_paper.CONFIG.algo.replace(s=sz.s))
    device = smoke.run(1, sz, expect_impl="pallas_interpret",
                       platform="cpu")
    assert device == {"platform": "cpu", "kind": "cpu", "count": 1}
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split("]")[0] + "]" for ln in lines] == [
        "[device]", "[data]", "[kernels]", "[fit]", "[streaming]", "[serve]"]


def test_a_demotion_fails_the_run(smoke):
    ops.record_demotion("fused", "pallas_interpret", (1, 64, 25, 28), "f32",
                        RuntimeError("injected"))
    with pytest.raises(smoke.SmokeFailure, match=r"fused \(1, 64, 25, 28\)"):
        smoke._no_demotions("fit")
