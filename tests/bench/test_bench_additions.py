"""A configuration and its cell join the benchmark by new files and entries
alone.  A checkout is built from ``BENCHMARK.json`` and ``bench/`` as they
are, plus a synthetic configuration added the way a new one is added
(``tests/bench/synthetic/``): its ``<c>.json``, ``<c>.py`` and ``<c>_ref.py``
and its traffic file, new ``configs`` and ``workloads`` entries, and the
new cell's name appended to the ``workloads`` lists of the metrics it
reports.  Its answers are float32, compared by ``max_rel_error``."""

from __future__ import annotations

import filecmp
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, run, spec  # noqa: E402
from test_bench_wiring import (  # noqa: E402
    SEED, answers_failed, check_runs_and_is_correct, tiny_cell,
)

SYNTHETIC = Path(__file__).resolve().parent / "synthetic"
ENTRIES = json.loads((SYNTHETIC / "entries.json").read_text())
CELL = ENTRIES["workload"]["name"]
#: the metrics of a closed-loop cell, which list their cells
REPORTED = [
    "throughput", "dispatch_ms.offline", "device_idle_share.offline", "gemm_roofline", "mfu",
    "h2d_bytes_per_sample.offline", "host_op_share.offline",
]


def _metrics(bench: dict) -> list[dict]:
    return bench["end_to_end"] + bench["per_layer"]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    for kind in ("configs", "traffic"):
        for f in sorted((SYNTHETIC / kind).iterdir()):
            assert not (root / "bench" / kind / f.name).exists(), f"{f.name} is not new"
            shutil.copy(f, root / "bench" / kind / f.name)
    bench = spec.load_benchmark(ROOT)
    bench["configs"].append(ENTRIES["config"])
    bench["workloads"].append(ENTRIES["workload"])
    for m in _metrics(bench):
        if m["name"] in REPORTED:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    return root


def test_added_cell_edits_no_file_and_keeps_the_layout(checkout):
    # every file of the benchmark is as it was
    for f in (ROOT / "bench").rglob("*"):
        if f.is_file() and ".cache" not in f.parts and "__pycache__" not in f.parts:
            assert filecmp.cmp(f, checkout / f.relative_to(ROOT), shallow=False), f
    # BENCHMARK.json: the two entries and the appended names, nothing else
    bench = spec.load_benchmark(checkout)
    assert bench["configs"].pop() == ENTRIES["config"]
    assert bench["workloads"].pop() == ENTRIES["workload"]
    for m in _metrics(bench):
        if m["name"] in REPORTED:
            assert m["workloads"].pop() == CELL
    assert bench == spec.load_benchmark(ROOT)
    assert spec.layout_errors(spec.load_benchmark(checkout)) == []


def test_added_cell_is_found_by_name(checkout):
    cell = spec.load_cell(CELL, root=checkout)
    assert cell.root == checkout and cell.config["name"] == "dense_f32"
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "throughput"]
    assert {m["name"] for m in cell.per_layer} == {"compile_s", "warm_s"} | set(REPORTED[1:])
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.metric_reader(m["name"], checkout))


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_added_cell_runs_and_is_correct(cpu, checkout, trace):
    cell = tiny_cell(CELL, checkout)
    result, lines = run.measure(cell, SEED, 0.4, trace, devices=cpu)
    check_runs_and_is_correct(cell, result, lines, trace)
    assert list(result["check"]) == ["max_rel_error", "unanswered"]
    assert any(line.startswith("check: max_rel_error ") for line in lines)


@pytest.mark.parametrize("form", ["control", "bf16_answers", "fault", "half_batch"])
def test_added_cell_control_and_lower_precision_are_not_correct(cpu, checkout, form):
    cell = tiny_cell(CELL, checkout)
    result, lines = run.measure(cell, SEED + 1, 0.3, False, devices=cpu,
                                wrap=control.FORMS[form](cell))
    assert result["correct"] is False, lines
    assert answers_failed(result) == ["max_rel_error"], lines
