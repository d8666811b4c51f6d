"""BENCHMARK.json against its contract, and every cell's files found by
name: configuration, traffic, knee, tier, reference and metric readers."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import peaks, spec  # noqa: E402

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and re.fullmatch(r"[A-Za-z0-9_./-]+", p)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_and_units(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert callable(spec.reader(m["name"]))


def test_every_name_is_unique_and_allowed():
    names = ([m["name"] for m in METRICS] + CELLS
             + [c["name"] for c in BENCH["configs"]])
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert NAME.match(w["config"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200


def test_end_to_end_bounds_and_setup():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_per_layer_moves_a_metric_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", CELLS):
            assert w in CELLS
            assert "workloads" not in moved or w in moved["workloads"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve_by_name(name):
    c = spec.cell(name)
    assert c.config["name"] == c.workload["config"]
    assert c.knee["knee_rps"] > 0
    assert 0 < c.traffic["remote_fraction_budget"] <= 1
    spec.module("tiers", c.config["tier"])
    assert callable(spec.module("references", c.config["reference"]).logits)
    assert {"setup_s"} <= {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert set(c.config["limits"]) >= {"cascade_mismatch", "answer_gap",
                                       "conf_err", "escalation_order",
                                       "pallas_gate_missing"}


@pytest.mark.parametrize("cfg", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_files(cfg):
    file = ROOT / cfg["file"]
    assert file.is_file() and cfg["file"].startswith("chipbench/")
    body = spec.load_json(file)
    assert body["name"] == cfg["name"] and body["reduced"] == cfg["reduced"]
    assert any(cfg["name"] == w["config"] for w in BENCH["workloads"])


def test_unknown_device_has_no_peaks():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
