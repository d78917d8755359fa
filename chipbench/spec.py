"""Finds a cell's parts by name, from files alone.

``BENCHMARK.json`` names the cells; each cell names a configuration and a
traffic mix, and the benchmark names its per-layer metrics. Their files
sit under this directory at fixed places:

    configs/<config>.json    the deployment: shapes, scale, guarantees
    traffic/<traffic>.json   the mix's parameters, read by `load.py`
    metrics/<metric>.py      the metric's reader, ``read(run) -> float | None``

So a later change adds a configuration, a mix or a metric by adding a
file and an entry, and edits nothing that is here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list  # the BENCHMARK.json entries this cell reports
    per_layer: list
    readers: dict  # per-layer metric name -> read(run) callable


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, base: pathlib.Path = HERE):
    path = base / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    if mod_spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, *, bench_path: pathlib.Path = ROOT / "BENCHMARK.json",
              base: pathlib.Path = HERE) -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_path}; have {sorted(cells)}")
    w = cells[name]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(base / "configs" / f"{w['config']}.json"),
        mix=load_json(base / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=per_layer,
        readers={m["name"]: load_reader(m["name"], base) for m in per_layer},
    )
