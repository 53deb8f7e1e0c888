"""Each kind of cell, driven end to end on the CPU with Pallas in
interpret mode, at widths cut for the test (the cells run at published
widths on the chip).  The harness's look for a chip is skipped by handing
``measure`` the CPU device; everything after it runs as on the chip: the
compile through ``repro.compile``, warm-up, the open or closed loop, the
reader of every metric, and the comparison with the plain reference.

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


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    if cell.config["name"] == "toycar":
        cell.config["layer_widths"] = [64, 16, 16, 8, 16, 64]
    else:
        cell.config.update(hidden_size=64, num_attention_heads=4, ffn_dim=128,
                           num_hidden_layers=2)
        cell.traffic.update(seq_len=16, samples_per_call=2, buckets=[2])
    if cell.traffic["loop"] == "open":
        cell.traffic["rate_per_s"] = 200
    return cell


@pytest.fixture(scope="module")
def cpu(tmp_path_factory):
    """The CPU device, with the run's caches under a temporary directory;
    JAX's cache settings are put back for the tests that follow."""
    import jax

    saved_cache = run.CACHE
    saved = {k: jax.config.values[k] for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs", "jax_compilation_cache_max_size")}
    run.CACHE = tmp_path_factory.mktemp("bench_cache")
    yield jax.devices("cpu")
    run.CACHE = saved_cache
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


CELLS = ["toycar.stream", "musicgen.prefill512"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_cell_kind_runs_and_is_correct(cpu, name, trace):
    cell = tiny_cell(name)
    result, lines = run.measure(cell, SEED, 0.4, trace, devices=cpu)
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "check"
    assert result["device"]["platform"] == "cpu"
    want = {m["name"] for m in cell.metrics(trace)}
    # no device metric is read from a CPU run: there is no chip in the trace
    device_only = {"device_idle_share.stream", "device_idle_share.offline", "gemm_roofline", "mfu"}
    assert set(result["metrics"]) == want - device_only
    assert lines[-1].endswith("correct True")
    if trace:
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("form", ["control", "fault", "half_batch"])
def test_control_and_altered_answer_are_not_correct(cpu, name, form):
    cell = tiny_cell(name)
    result, lines = run.measure(cell, SEED + 1, 0.3, False, devices=cpu,
                                wrap=control.FORMS[form](cell))
    assert result["correct"] is False, lines
    assert result["check"]["wrong_elements"]["value"] > 0


def test_same_seed_same_inputs_and_weights(cpu):
    import numpy as np

    cell = tiny_cell("musicgen.prefill512")
    shape = cell.model.sample_shape(cell.config, cell.traffic)
    a = cell.model.make_params(cell.config, run.jax_key(SEED), shape)
    b = cell.model.make_params(cell.config, run.jax_key(SEED), shape)
    c = cell.model.make_params(cell.config, run.jax_key(SEED + 2**32), shape)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["l0.w_q"], c["l0.w_q"])
