"""Every cell of ``BENCHMARK.json``, driven end to end on the CPU with
Pallas in interpret mode, at the sizes its configuration's ``test_sizes``
cuts it to (the cells run at published widths on the chip).  The
harness's look for a chip is skipped by handing ``measure`` the CPU
device; everything after it runs as on the chip: the compile through
``repro.compile``, warm-up, the open or closed loop, the reader of every
metric, and the comparison with the plain reference.

The comparison has to fail the control (the reference with int4 weights
in the program's place), an answer altered where it is produced, and a
batch of which half was left out."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, run, spec  # noqa: E402

SEED = 2**33 + 12345  # wider than 32 bits, as a run's seed may be


def tiny_cell(name: str, root: Path = ROOT) -> spec.Cell:
    cell = spec.load_cell(name, root=root)
    cell.model.test_sizes(cell.config, cell.traffic)
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_per_s"] = 200
    return cell


def needs_chip(metric: dict) -> bool:
    """A metric no CPU run reads: from the device trace (a CPU trace holds
    no chip), or a share of the chip's peak (``mfu``)."""
    return metric["source"] == "device_trace" or "mfu" in metric["name"]


def check_runs_and_is_correct(cell, result, lines, trace):
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "check"
    assert result["device"]["platform"] == "cpu"
    want = {m["name"] for m in cell.metrics(trace) if not needs_chip(m)}
    assert set(result["metrics"]) == want
    assert lines[-1].endswith("correct True")
    if trace:
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def answers_failed(result) -> list[str]:
    """The numbers comparing answers that exceed their limits."""
    return [k for k, v in result["check"].items()
            if k != "unanswered" and not float(v["value"]) <= v["limit"]]


CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_cell_kind_runs_and_is_correct(cpu, name, trace):
    cell = tiny_cell(name)
    result, lines = run.measure(cell, SEED, 0.4, trace, devices=cpu)
    check_runs_and_is_correct(cell, result, lines, trace)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("form", ["control", "fault", "half_batch"])
def test_control_and_altered_answer_are_not_correct(cpu, name, form):
    cell = tiny_cell(name)
    result, lines = run.measure(cell, SEED + 1, 0.3, False, devices=cpu,
                                wrap=control.FORMS[form](cell))
    assert result["correct"] is False, lines
    assert answers_failed(result), lines


def test_same_seed_same_inputs_and_weights(cpu):
    import numpy as np

    cell = tiny_cell("musicgen.prefill512")
    shape = cell.model.sample_shape(cell.config, cell.traffic)
    a = cell.model.make_params(cell.config, run.jax_key(SEED), shape)
    b = cell.model.make_params(cell.config, run.jax_key(SEED), shape)
    c = cell.model.make_params(cell.config, run.jax_key(SEED + 2**32), shape)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["l0.w_q"], c["l0.w_q"])
