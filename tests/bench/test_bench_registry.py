"""A later change adds a configuration, a traffic kind and a per-layer
metric as new files only: here all three live in a temporary benchmark
root and run through the unchanged harness."""
import json
import textwrap

import pytest

from bench import run, spec

KIND = textwrap.dedent('''
    """A made-up traffic kind: repeated matrix products on the device."""
    import time

    import jax
    import jax.numpy as jnp


    class Kind:
        def __init__(self, cell, seed, overrides=None):
            self.n = cell.config["dataset"]["n"]
            self.reps = cell.mix["reps"]
            self.seed = seed

        def setup(self, seconds):
            key = jax.random.PRNGKey(self.seed)
            self.x = jax.random.normal(key, (self.n, self.n))
            self.f = jax.jit(lambda x: x @ x)
            self.f(self.x).block_until_ready()

        def window(self, seconds):
            t0 = time.monotonic()
            self.done = 0
            while time.monotonic() - t0 < seconds:
                for _ in range(self.reps):
                    self.out = self.f(self.x)
                self.out.block_until_ready()
                self.done += 1
            self.window_s = time.monotonic() - t0

        def end_to_end(self):
            return {"step_s": self.window_s / self.done}

        def attempted_failed(self):
            return self.done, 0

        def counters(self):
            return {"steps": self.done}

        def release(self):
            pass

        def check(self):
            ref = self.x @ self.x
            err = float(jnp.max(jnp.abs(self.out - ref)))
            return [("max_abs_err", err, 1e-3)]
''')

READER = textwrap.dedent('''
    def read(ctx):
        return float(ctx["counters"]["steps"])
''')


def test_new_config_kind_and_metric_run_from_their_own_files(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "kinds", "metrics"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "square.json").write_text(json.dumps(
        {"name": "square", "dataset": {"n": 64}, "limits": {}}))
    (bench / "traffic" / "repeat.json").write_text(json.dumps(
        {"kind": "matmul_loop", "reps": 3}))
    (bench / "kinds" / "matmul_loop.py").write_text(KIND)
    (bench / "metrics" / "steps_seen.py").write_text(READER)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "square", "file": "bench/configs/square.json"}],
        "workloads": [{"name": "square.repeat", "config": "square",
                       "traffic": "repeat", "chips": 1}],
        "end_to_end": [
            {"name": "step_s", "unit": "s", "workloads": ["square.repeat"]},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "steps_seen", "unit": "steps",
                       "workloads": ["square.repeat"]}],
    }))
    cell = spec.load_cell("square.repeat", tmp_path)
    r = run.run_cell(cell, 3, 0.2, False, platform="cpu")
    assert r["correct"] and r["attempted"] >= 1
    assert set(r["metrics"]) == {"step_s", "setup_s"}
    reader = spec.metric_reader(cell, "steps_seen")
    assert reader({"counters": {"steps": 4}}) == 4.0


def test_every_cell_of_the_benchmark_finds_its_files():
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert hasattr(spec.kind_module(cell), "Kind")
        for m in cell.per_layer:
            assert callable(spec.metric_reader(cell, m["name"]))
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_the_serving_p99_reader_counts_failed_requests_above_every_limit():
    cell = spec.load_cell("hepmass.serve")
    read = spec.metric_reader(cell, "serve_p99_ms")
    lat = [1.0] * 99
    assert read({"counters": {"due_latency_ms": lat, "n_failed": 0}}) == 1.0
    assert read({"counters": {"due_latency_ms": lat, "n_failed": 2}}) == \
        float("inf")


def test_the_exchange_reader_takes_the_busiest_chip_per_job():
    from bench.trace import Reduction

    read = spec.metric_reader(spec.load_cell("hepmass.fit"), "exchange_ms.fit")
    red = Reduction(window_s=1.0, busy_s=[0.5] * 4, ops=[], idle_gaps=[],
                    collective_s=[0.010, 0.020, 0.015, 0.005])
    assert read({"reduction": red, "counters": {"jobs": 4}}) == \
        pytest.approx(5.0)
    red.collective_s = [0.0] * 4
    assert read({"reduction": red, "counters": {"jobs": 4}}) is None
