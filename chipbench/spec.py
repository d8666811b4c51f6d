"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell (``workloads`` entry) names a configuration and a traffic mix; the
harness then finds, with no code per cell:

* ``configs[].file``                  the configuration as it is run;
* ``chipbench/traffic/<traffic>.json`` the traffic mix's parameters;
* ``chipbench/cells/<workload>.json``  the cell's knee, from the sweep;
* ``chipbench/tiers/<tier>.py``        the local-tier builder the
  configuration's ``tier`` names;
* ``chipbench/references/<reference>.py`` its plain reference;
* ``chipbench/metrics/<metric>.py``    one reader per metric; a metric
  ``<name>.overload`` (the same quantity in a cell above the knee, where
  it moves another end-to-end metric) with no file of its own is read by
  ``<name>.py``.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent


@dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    knee: dict
    end_to_end: list            # metric entries this cell reports
    per_layer: list


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell(name: str, bench: dict | None = None, root: Path = ROOT) -> Cell:
    bench = bench or benchmark(root)
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(by_name)}")
    wl = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    return Cell(
        workload=wl,
        config=load_json(root / cfg_entry["file"]),
        traffic=load_json(HERE / "traffic" / f"{wl['traffic']}.json"),
        knee=load_json(HERE / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def module(kind: str, name: str):
    """``chipbench.<kind>.<name>`` (tiers, references)."""
    return importlib.import_module(f"chipbench.{kind}.{name}")


OVERLOAD = ".overload"


def reader(metric: str):
    """The ``read(run)`` function of ``chipbench/metrics/<metric>.py``
    (metric names may hold dots, so the file is loaded by path)."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists() and metric.endswith(OVERLOAD):
        return reader(metric[:-len(OVERLOAD)])
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
