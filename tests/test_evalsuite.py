"""The reproduction & regression harness (`repro.evalsuite`).

Covers the acceptance contract of the suite subsystem:
* schema round-trip validation (and that the validator actually rejects);
* ε / success-rate / time-to-target math against hand-computed values;
* gate pass/fail on synthetic regressions, including a non-zero exit
  against the *committed* baseline artifact;
* determinism of registry dataset generation (same spec ⇒ bitwise
  identical memmap);
* a miniature end-to-end suite run through `repro.api.fit`.
"""
import copy
import json
import math
import os

import numpy as np
import pytest

from repro.evalsuite import datasets as ds
from repro.evalsuite import gate, metrics, schema, suite

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "results", "BENCH_baseline.json")


def _suite_doc() -> dict:
    """A minimal hand-built, schema-valid BENCH_suite document."""
    rows = [
        {"dataset": "d0", "method": "bm/sequential", "kind": "bigmeans",
         "seed": s, "f_full": f, "epsilon": (f - 100.0) / 100.0,
         "success": f <= 105.0, "wall_s": w, "n_chunks": 8,
         "n_iterations": 40, "n_accepted": 3}
        for s, f, w in [(0, 100.0, 2.0), (1, 104.0, 1.0), (2, 110.0, 3.0)]
    ]
    cells = [metrics.aggregate_cell("d0", "bm/sequential", "bigmeans", rows,
                                    success_tol=0.05)]
    return schema.envelope(
        "suite", rows, tier="quick", success_tol=0.05, protocol="test",
        datasets=[{"name": "d0", "paper_name": "kegg", "m": 1000, "n": 20,
                   "k": 5, "s": 100, "n_chunks": 8, "f_star": 100.0}],
        cells=cells)


# ---------------------------------------------------------------- schema

class TestSchema:
    def test_roundtrip_valid(self):
        doc = json.loads(json.dumps(_suite_doc()))
        assert schema.validate(doc, schema.SUITE_SCHEMA) == []
        assert schema.validate(doc, schema.ENVELOPE_SCHEMA) == []

    @pytest.mark.parametrize("mutate, fragment", [
        (lambda d: d.pop("cells"), "missing required field 'cells'"),
        (lambda d: d.update(schema_version="bogus/9"), "expected"),
        (lambda d: d.update(tier="weekly"), "not in"),
        (lambda d: d["rows"][0].update(wall_s="fast"), "expected type"),
        (lambda d: d["rows"][0].pop("epsilon"), "missing required"),
        (lambda d: d.update(rows=[]), ">= 1 items"),
        (lambda d: d["cells"][0].update(success_rate=-0.5), "minimum"),
    ])
    def test_rejects_corruptions(self, mutate, fragment):
        doc = _suite_doc()
        mutate(doc)
        errors = schema.validate(doc, schema.SUITE_SCHEMA)
        assert errors and any(fragment in e for e in errors), errors

    def test_check_raises_with_every_error(self):
        doc = _suite_doc()
        del doc["rows"][0]["epsilon"], doc["rows"][1]["f_full"]
        with pytest.raises(ValueError, match="2 error"):
            schema.check(doc, schema.SUITE_SCHEMA)

    def test_unknown_schema_keyword_is_programming_error(self):
        with pytest.raises(ValueError, match="unsupported schema keywords"):
            schema.validate({}, {"type": "object", "patternProperties": {}})

    def test_write_bench_refuses_invalid(self, tmp_path):
        doc = schema.envelope("x", rows=[{"a": 1}])
        del doc["host"]
        with pytest.raises(ValueError, match="host"):
            schema.write_bench(str(tmp_path / "b.json"), doc)
        assert not (tmp_path / "b.json").exists()

    def test_committed_bench_artifacts_are_schema_valid(self):
        for name in ("BENCH_batched.json", "BENCH_precision.json",
                     "BENCH_engine.json"):
            path = os.path.join(REPO, name)
            if not os.path.exists(path):
                continue
            with open(path) as f:
                doc = json.load(f)
            # migrated onto the shared envelope in this PR; older artifacts
            # regenerate on the next benchmark run
            if doc.get("schema_version") == schema.SCHEMA_VERSION:
                assert schema.validate(doc, schema.ENVELOPE_SCHEMA) == []


# --------------------------------------------------------------- metrics

class TestMetrics:
    def test_relative_error(self):
        assert metrics.relative_error(110.0, 100.0) == pytest.approx(0.10)
        assert metrics.relative_error(95.0, 100.0) == pytest.approx(-0.05)
        with pytest.raises(ValueError):
            metrics.relative_error(1.0, 0.0)
        with pytest.raises(ValueError):
            metrics.relative_error(1.0, math.nan)

    def test_success_rate(self):
        assert metrics.success_rate([0.01, 0.2, 0.04], 0.05) == pytest.approx(2 / 3)
        assert metrics.success_rate([0.5], 0.05) == 0.0
        assert metrics.success_rate([math.nan, 0.0], 0.05) == 0.5
        with pytest.raises(ValueError):
            metrics.success_rate([], 0.05)

    def test_time_to_target_curve(self):
        runs = [(2.0, True), (1.0, True), (3.0, False)]
        # grid defaults to the successful runs' own wall times
        assert metrics.time_to_target_curve(runs) == [
            [1.0, 1 / 3], [2.0, 2 / 3]]
        assert metrics.time_to_target_curve(runs, grid=[0.5, 10.0]) == [
            [0.5, 0.0], [10.0, 2 / 3]]
        # nothing succeeded: one flat zero point at the slowest run
        assert metrics.time_to_target_curve([(4.0, False)]) == [[4.0, 0.0]]

    def test_aggregate_cell_hand_computed(self):
        rows = [
            {"epsilon": 0.00, "wall_s": 2.0, "success": True},
            {"epsilon": 0.04, "wall_s": 1.0, "success": True},
            {"epsilon": 0.10, "wall_s": 3.0, "success": False},
        ]
        cell = metrics.aggregate_cell("d", "m", "bigmeans", rows,
                                      success_tol=0.05)
        assert cell["epsilon_mean"] == pytest.approx(0.14 / 3)
        assert cell["epsilon_min"] == 0.0
        assert cell["epsilon_max"] == pytest.approx(0.10)
        assert cell["success_rate"] == pytest.approx(2 / 3)
        assert cell["wall_mean_s"] == pytest.approx(2.0)
        assert cell["time_to_target"] == [[1.0, 1 / 3], [2.0, 2 / 3]]


# ------------------------------------------------------------------ gate

class TestGate:
    def test_identical_docs_pass(self):
        doc = _suite_doc()
        result = gate.compare(doc, copy.deepcopy(doc))
        assert result.ok and result.checked == 1
        assert "PASS" in result.report()

    def test_eps_regression_fails(self):
        base, fresh = _suite_doc(), _suite_doc()
        fresh["cells"][0]["epsilon_mean"] += 0.06      # > default 0.05 tol
        result = gate.compare(base, fresh)
        assert not result.ok
        assert any("epsilon_mean" in f for f in result.failures)

    def test_eps_improvement_only_warns(self):
        base, fresh = _suite_doc(), _suite_doc()
        fresh["cells"][0]["epsilon_mean"] -= 0.06
        result = gate.compare(base, fresh)
        assert result.ok
        assert any("improved" in w for w in result.warnings)

    def test_success_drop_fails(self):
        base, fresh = _suite_doc(), _suite_doc()
        fresh["cells"][0]["success_rate"] = 0.0        # baseline is 2/3
        result = gate.compare(base, fresh)
        assert not result.ok
        assert any("success_rate" in f for f in result.failures)

    def test_wall_regression_fails_and_no_wall_skips(self):
        base, fresh = _suite_doc(), _suite_doc()
        fresh["cells"][0]["wall_mean_s"] *= 3.0        # > default 2.5x
        assert not gate.compare(base, fresh).ok
        assert gate.compare(base, fresh, check_wall=False).ok

    def test_wall_floor_exempts_fast_cells(self):
        base, fresh = _suite_doc(), _suite_doc()
        base["cells"][0]["wall_mean_s"] = 0.01
        fresh["cells"][0]["wall_mean_s"] = 0.4          # 40x but tiny
        assert gate.compare(base, fresh).ok

    def test_missing_cell_fails_new_cell_warns(self):
        base, fresh = _suite_doc(), _suite_doc()
        extra = copy.deepcopy(fresh["cells"][0])
        extra["method"] = "bm/new"
        fresh["cells"].append(extra)
        assert any("new cell" in w for w in gate.compare(base, fresh).warnings)
        fresh["cells"] = [extra]                       # original cell gone
        result = gate.compare(base, fresh)
        assert any("missing from fresh" in f for f in result.failures)

    def test_schema_invalid_artifact_fails_gate(self):
        base, fresh = _suite_doc(), _suite_doc()
        del fresh["cells"][0]["epsilon_mean"]
        result = gate.compare(base, fresh)
        assert not result.ok
        assert any("schema-invalid" in f for f in result.failures)

    def test_gate_cli_exits_nonzero_vs_committed_baseline(self, tmp_path):
        """Acceptance: ε degraded beyond tolerance vs the COMMITTED
        baseline makes `python -m repro.evalsuite.gate` exit non-zero."""
        assert os.path.exists(BASELINE), "committed baseline must exist"
        with open(BASELINE) as f:
            fresh = json.load(f)
        report = tmp_path / "report.txt"

        # unmodified re-run of the committed artifact passes
        ok_path = tmp_path / "fresh_ok.json"
        ok_path.write_text(json.dumps(fresh))
        assert gate.main(["--baseline", BASELINE, "--fresh", str(ok_path),
                          "--report", str(report)]) == 0

        # degrade every cell's ε beyond tolerance -> exit 1 + report
        for cell in fresh["cells"]:
            cell["epsilon_mean"] += 0.2
        bad_path = tmp_path / "fresh_bad.json"
        bad_path.write_text(json.dumps(fresh))
        rc = gate.main(["--baseline", BASELINE, "--fresh", str(bad_path),
                        "--report", str(report)])
        assert rc == 1
        assert "FAIL" in report.read_text()


# -------------------------------------------------- datasets / registry

class TestDatasets:
    def test_registry_tiers(self):
        quick = ds.list_datasets("quick")
        assert quick and set(quick) <= set(ds.list_datasets("full")), \
            "quick datasets must be a subset of full: nightly must cover " \
            "every PR-gated cell"
        with pytest.raises(KeyError, match="unknown dataset"):
            ds.get_dataset("nope")

    def test_quick_registry_f_star_committed(self):
        for name in ds.list_datasets("quick"):
            assert ds.get_dataset(name).f_star is not None, \
                f"{name}: the PR gate needs a committed f_star"

    def test_memmap_generation_deterministic(self, tmp_path):
        """Same spec ⇒ bitwise-identical memmap (the registry's contract:
        every run and CI job clusters byte-identical data)."""
        from repro.data.synthetic import GMMSpec, gmm_dataset, gmm_memmap

        spec = GMMSpec(m=2048, n=7, components=4, seed=9)
        a = gmm_memmap(spec, str(tmp_path / "a.npy"))
        b = gmm_memmap(spec, str(tmp_path / "b.npy"))
        with open(a, "rb") as fa, open(b, "rb") as fb:
            assert fa.read() == fb.read()
        # and the memmap holds the same rows the in-core path generates
        np.testing.assert_array_equal(
            np.load(a), np.asarray(gmm_dataset(spec))[:, :])

    def test_materialize_reuses_existing_file(self, tmp_path):
        spec = ds.DatasetSpec(name="t-tiny", paper_name="kegg", m=1024, n=20,
                              components=4, k=3, s=128, n_chunks=4)
        p1 = ds.materialize(spec, str(tmp_path))
        mtime = os.path.getmtime(p1)
        p2 = ds.materialize(spec, str(tmp_path))
        assert p1 == p2 and os.path.getmtime(p2) == mtime

    def test_dataset_record_is_schema_valid(self):
        for name in ds.list_datasets():
            record = ds.get_dataset(name).to_record()
            assert schema.validate(record, schema._DATASET_SCHEMA) == [], name


# ------------------------------------------------------- suite (end-to-end)

class TestSuiteRun:
    @pytest.fixture(scope="class")
    def mini_doc(self, tmp_path_factory):
        """One tiny dataset x (one strategy + one baseline) x 2 seeds."""
        spec = ds.DatasetSpec(name="t-mini", paper_name="kegg", m=1536, n=20,
                              components=6, k=4, s=192, n_chunks=4,
                              f_star=None, tiers=("quick",))
        ds.REGISTRY[spec.name] = spec
        try:
            yield suite.run_suite(
                "quick", seeds=(0, 1), dataset_names=["t-mini"],
                method_names=["bm/sequential", "baseline/forgy"],
                data_root=str(tmp_path_factory.mktemp("evalsuite")),
                verbose=False)
        finally:
            del ds.REGISTRY[spec.name]

    def test_doc_schema_valid(self, mini_doc):
        assert schema.validate(mini_doc, schema.SUITE_SCHEMA) == []

    def test_equal_budget_and_bootstrap_f_star(self, mini_doc):
        (record,) = mini_doc["datasets"]
        assert record["f_star_source"].startswith("run-best")
        best = min(r["f_full"] for r in mini_doc["rows"])
        assert record["f_star"] == best
        eps_best = min(r["epsilon"] for r in mini_doc["rows"])
        assert eps_best == pytest.approx(0.0)
        for r in mini_doc["rows"]:
            assert r["success"] == (r["epsilon"] <= mini_doc["success_tol"])
        # the big-means rows consumed exactly the registry chunk budget
        for r in mini_doc["rows"]:
            if r["kind"] == "bigmeans":
                assert r["n_chunks"] == 4

    def test_cells_cover_matrix(self, mini_doc):
        keys = {(c["dataset"], c["method"]) for c in mini_doc["cells"]}
        assert keys == {("t-mini", "bm/sequential"),
                        ("t-mini", "baseline/forgy")}

    def test_write_outputs(self, mini_doc, tmp_path):
        json_path = str(tmp_path / "BENCH_suite.json")
        csv_path = str(tmp_path / "runs.csv")
        suite.write_outputs(mini_doc, json_path, csv_path)
        with open(json_path) as f:
            assert schema.validate(json.load(f), schema.SUITE_SCHEMA) == []
        with open(csv_path) as f:
            lines = f.read().strip().splitlines()
        assert len(lines) == 1 + len(mini_doc["rows"])

    def test_unknown_method_name_raises(self):
        with pytest.raises(KeyError, match="unknown methods"):
            suite.run_suite("quick",
                            method_names=["bm/seqential", "baseline/forgy"])

    def test_host_fleet_cells_refused_on_an_accelerator(self, monkeypatch):
        """The suite process holds the chip, so its 2-process cells could
        not get it: refused before any cell runs."""
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="bm/hostmesh-2p"):
            suite.run_suite("quick", method_names=["bm/hostmesh-2p"])

    def test_method_matrix_meets_acceptance(self):
        """The quick tier must cover >= 2 big-means strategies and >= 3
        baselines (ISSUE 5 acceptance criteria)."""
        quick = [m for m in suite.METHODS if "quick" in m.tiers]
        strategies = {m.method for m in quick if m.kind == "bigmeans"}
        baselines = [m for m in quick if m.kind == "baseline"]
        assert len(strategies) >= 2
        assert len(baselines) >= 3


# ------------------------------------------------------------- api hooks

class TestFitRowHook:
    def test_fit_records_dispatch_extras_and_to_row(self):
        import jax

        from repro.api import BigMeansConfig, fit

        X = np.asarray(
            jax.random.normal(jax.random.PRNGKey(0), (512, 8)))
        cfg = BigMeansConfig(k=4, s=64, n_chunks=4, seed=7)
        res = fit(X, cfg, method="sequential")
        assert res.extras["fit"]["method"] == "sequential"
        assert res.extras["fit"]["seed"] == 7
        assert res.extras["fit"]["source"] == "ArraySource"
        row = res.to_row()
        json.dumps(row)                      # JSON-safe by contract
        assert row["algorithm"] == "big_means"
        assert row["fit"]["impl"] == cfg.resolved_impl()
