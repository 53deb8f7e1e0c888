"""BENCHMARK.json and the files it names: every cell, configuration,
traffic mix and metric is found by name, and the file keeps the benchmark
contract's shape."""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()


def test_cells_in_order_on_one_chip():
    # the first two cells stay first, so that neither is dropped quietly;
    # a configuration and its cells join after them
    assert CELLS[:2] == ["toycar.stream", "musicgen.prefill512"]
    assert spec.layout_errors(BENCH) == []


LAYOUT_FAULTS = {
    "cell_twice": (lambda b: b["workloads"].append(dict(b["workloads"][0])),
                   "cell 'toycar.stream' appears 2"),
    "config_unused": (lambda b: b["workloads"][0].update(config="nope"),
                      "configuration 'toycar' has no cell"),
    "two_chips": (lambda b: b["workloads"][1].update(chips=2), "asks for 2 chips"),
    "all_on_four": (lambda b: [w.update(chips=4) for w in b["workloads"]],
                    "2 of 2 cells on 4 chips"),
    "unknown_cell": (lambda b: b["per_layer"][-1]["workloads"].append("no.cell"),
                     "lists unknown cell"),
    "too_many_cells": (lambda b: b["workloads"].extend(
        dict(b["workloads"][0], name=f"c{i}", traffic=f"t{i}") for i in range(23)), "25 cells"),
}


@pytest.mark.parametrize("change, error", LAYOUT_FAULTS.values(), ids=list(LAYOUT_FAULTS))
def test_layout_errors_name_what_is_wrong(change, error):
    bench = json.loads(json.dumps(BENCH))
    change(bench)
    assert any(error in e for e in spec.layout_errors(bench)), spec.layout_errors(bench)


def test_end_to_end_metrics_are_the_four():
    assert [m["name"] for m in BENCH["end_to_end"]] == [
        "setup_s", "latency_p50_ms", "throughput"
    ]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_entry_shape_and_reader(metric):
    allowed = {"name", "unit", "better", "source"}
    allowed |= {"bound"} if "bound" in metric else {"layer", "moves"}
    assert set(metric) - {"workloads"} == allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert callable(spec.metric_reader(metric["name"]))
    for cell in metric.get("workloads", []):
        assert cell in CELLS


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_a_metric_its_cells_report(metric):
    moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    cells = metric.get("workloads", CELLS)
    assert set(cells) <= set(moved.get("workloads", CELLS))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_found_by_name(cell):
    c = spec.load_cell(cell)
    entry = {w["name"]: w for w in BENCH["workloads"]}[cell]
    assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
    assert 1 <= len(entry["why"]) <= 200
    assert c.traffic["loop"] in ("open", "closed")
    assert callable(c.model.model_fn) and callable(c.ref.reference)
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1


@pytest.mark.parametrize("config", BENCH["configs"], ids=[c["name"] for c in BENCH["configs"]])
def test_config_file_states_its_cut(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    data = json.loads((ROOT / config["file"]).read_text())
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    for key in ("assumed", "departures", "deployment", "check"):
        assert data[key]
    for key in config["reduced"]:
        assert key in data and key in data.get("published", {})
        assert not key.endswith(("_dim", "_rank", "_size"))


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="unknown workload"):
        spec.load_cell("no.such.cell")
