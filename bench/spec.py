"""Finding a cell's pieces by name.

Everything that belongs to one configuration, traffic mix, traffic kind or
per-layer metric is a file of its own under the benchmark's root:

* ``BENCHMARK.json`` — the cells and metrics;
* ``bench/configs/<config>.json`` — a deployment's sizes and guarantees;
* ``bench/traffic/<traffic>.json`` — a mix's parameters, whose ``kind``
  names the general kind that reads it;
* ``bench/kinds/<kind>.py`` — a kind of traffic (set-up, window, reference check);
* ``bench/metrics/<metric>.py`` — a per-layer reader, ``read(ctx)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list        # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    root: pathlib.Path


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def kind_module(cell: Cell):
    kind = cell.mix["kind"]
    return _load_module(cell.root / "bench" / "kinds" / f"{kind}.py",
                        f"bench_kind_{kind}")


def metric_reader(cell: Cell, metric: str):
    mod = _load_module(cell.root / "bench" / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_"))
    return mod.read
