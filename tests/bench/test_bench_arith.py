"""The benchmark's arithmetic, on the CPU: operation and byte counts from
the configurations' shapes, the peaks table, percentiles over every
request timed from its due time, the arrival schedule, the readers
that turn a run into metrics, and the measures of the comparison that
decides ``correct``."""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import check, counting, device, generator, spec  # noqa: E402
from bench.record import Run, percentile  # noqa: E402


def test_toycar_gemm_ops_per_window():
    """640-128x4-8-128x4-640: 264,192 multiply-adds, 2 operations each."""
    cell = spec.load_cell("toycar.stream")
    gemms = cell.model.gemms(cell.config, (1, 640), 1)
    assert counting.total_ops(gemms) == 528_384
    assert len(gemms) == 10


def test_musicgen_layer_ops_per_token():
    cell = spec.load_cell("musicgen.prefill512")
    cfg = cell.config
    seq = 512
    gemms = cell.model.gemms(cfg, (seq, 1536), 1)
    layer0 = [g for g in gemms if g.name.startswith("l0.")]
    dense = [g for g in layer0 if g.name not in ("l0.scores", "l0.context")]
    assert counting.total_ops(dense) / seq == 56_623_104  # projections + FFN
    attn = [g for g in layer0 if g.name in ("l0.scores", "l0.context")]
    assert counting.total_ops(attn) == 4 * seq * seq * 1536
    assert len(gemms) == 8 * cfg["num_hidden_layers"]


def test_gemm_bytes_and_ideal_time():
    g = counting.Gemm("g", 1024, 1536, 6144, bias=True, residual=True)
    assert g.bytes() == 1024 * 1536 + 1536 * 6144 + 1024 * 6144 * 2 + 4 * 6144
    peak_ops, peak_bw = 393e12, 819e9
    assert g.ideal_s(peak_ops, peak_bw) == pytest.approx(g.ops() / peak_ops)
    thin = counting.Gemm("thin", 1, 640, 128)  # one row: memory bound
    assert thin.ideal_s(peak_ops, peak_bw) == pytest.approx(thin.bytes() / peak_bw)


def test_peaks_table_and_unknown_device():
    p = device.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(device.NoChip, match="no peaks"):
        device.peaks("TPU v99")


@pytest.mark.parametrize(
    "q, want", [(50, 5.0), (95, 10.0), (90, 9.0), (10, 1.0), (100, 10.0)]
)
def test_nearest_rank_percentile(q, want):
    values = np.arange(10, 0, -1, dtype=float)  # unsorted on purpose
    assert percentile(values, q) == want


def _open_run(due, done, dispatched=None):
    calls = generator.Calls()
    calls.add(0.0, 0.002, 3)
    calls.add(0.010, 0.014, 2)
    return Run(
        "open", {}, {}, None, (1, 640), {}, 1.0, 0.5, 0.25, window_s=1.0, samples=5,
        calls=calls, latencies_s=np.asarray(done) - np.asarray(due),
        queue_waits_s=None if dispatched is None else np.asarray(dispatched) - np.asarray(due),
        batches=(5, 2, 16),
    )


def test_latency_readers_time_every_request_from_its_due_time():
    due = np.arange(20) * 0.001
    done = due + np.r_[np.full(19, 0.002), 0.050]  # one slow request
    run = _open_run(due, done)
    assert spec.metric_reader("latency_p50_ms")(run) == pytest.approx(2.0)
    assert spec.metric_reader("latency_p95_ms.stream")(run) == pytest.approx(2.0)
    done[-2] = due[-2] + 0.040  # two slow requests reach the 95th percentile
    assert spec.metric_reader("latency_p95_ms.stream")(_open_run(due, done)) == pytest.approx(40.0)


def test_serving_and_plan_readers():
    due = np.zeros(5)
    run = _open_run(due, due + 0.003, dispatched=[0.001, 0.001, 0.002, 0.004, 0.004])
    assert spec.metric_reader("queue_wait_p95_ms")(run) == pytest.approx(4.0)
    assert spec.metric_reader("batch_fill")(run) == pytest.approx(100 * 2.5 / 16)
    assert spec.metric_reader("dispatch_ms.stream")(run) == pytest.approx(3.0)
    assert spec.metric_reader("dispatch_ms.offline")(run) is None
    assert spec.metric_reader("throughput")(run) is None


def test_closed_loop_readers():
    cell = spec.load_cell("toycar.stream")
    calls = generator.Calls()
    for i in range(4):
        calls.add(i * 0.01, i * 0.01 + 0.005, 309)
    trace = SimpleNamespace(window_s=0.04, busy_s=0.01, gemm_s=0.002)
    run = Run(
        "closed", cell.config, cell.traffic, cell.model, (1, 640),
        device.peaks("TPU v5 lite"), 2.0, 1.0, 0.5, window_s=0.035, samples=4 * 309,
        calls=calls, trace=trace,
    )
    assert spec.metric_reader("throughput")(run) == pytest.approx(4 * 309 / 0.035)
    assert spec.metric_reader("dispatch_ms.offline")(run) == pytest.approx(5.0)
    assert spec.metric_reader("device_idle_share.offline")(run) == pytest.approx(75.0)
    assert spec.metric_reader("device_idle_share.stream")(run) is None
    mfu = spec.metric_reader("mfu")(run)
    assert mfu == pytest.approx(100 * 528_384 * 4 * 309 / 0.035 / 393e12)
    ideal = 4 * counting.ideal_s(cell.model.gemms(cell.config, (1, 640), 309), 393e12, 819e9)
    assert spec.metric_reader("gemm_roofline")(run) == pytest.approx(100 * ideal / 0.002)
    run.trace = None
    assert spec.metric_reader("gemm_roofline")(run) is None
    assert spec.metric_reader("device_idle_share.offline")(run) is None


def test_arrivals_are_seeded_and_at_the_rate():
    traffic = {"rate_per_s": 2000}
    a = generator.arrivals(traffic, 10.0, np.random.default_rng(2**33 + 1))
    b = generator.arrivals(traffic, 10.0, np.random.default_rng(2**33 + 1))
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a[-1] < 10.0
    assert len(a) == 20_000
    # another seed: the same gaps between arrivals, in another order
    c = generator.arrivals(traffic, 10.0, np.random.default_rng(7))
    assert not np.array_equal(a, c)
    gaps = np.diff(np.r_[a, 10.0])  # the last gap runs to the window's end
    assert np.allclose(np.sort(gaps), np.sort(np.diff(np.r_[c, 10.0])))
    # exponential gaps: mean 1/rate, and as many above the mean as e**-1 says
    assert gaps.mean() == pytest.approx(1 / 2000)
    assert np.mean(gaps > 1 / 2000) == pytest.approx(np.exp(-1), abs=0.01)


def test_attn_fallback_share_reads_the_window_counters():
    read = spec.metric_reader("attn_fallback_share")
    run = _open_run(np.zeros(2), np.ones(2))
    assert read(run) is None  # a run that took no counters
    run.counters = {"attn_epilogue_rows": 0, "attn_fallback_rows": 0}
    assert read(run) is None  # a cell with no fused attention epilogue
    run.counters = {"attn_epilogue_rows": 4864, "attn_fallback_rows": 135}
    assert read(run) == pytest.approx(100 * 135 / 4864)


def test_exact_measures_count_elements_and_missing_answers():
    want = np.arange(12, dtype=np.int8).reshape(3, 4)
    got = [want[0], want[1].copy(), None]
    got[1][2] += 1
    v = check.compare(got, want, {"wrong_elements": 0, "unanswered": 0})
    assert v.numbers == {"wrong_elements": (1, 0), "unanswered": (1, 0)}
    assert v.compared == 2 and not v.correct
    assert v.lines()[0] == "check: wrong_elements 1 limit 0"
    wrong_type = check.compare([want[0].astype(np.int32)], want[:1], {"wrong_elements": 0,
                                                                     "unanswered": 0})
    assert wrong_type.numbers["wrong_elements"] == (4, 0)


def test_float_measures_relative_to_each_answers_reference():
    want = np.asarray([[3.0, -4.0], [0.3, 0.4]], np.float32)  # RMS 3.5355, 0.35355
    got = want.copy()
    got[1, 0] += 0.01  # large beside its own answer, small beside the first
    limits = {"max_rel_error": 0.02, "rel_l2_error": 0.01, "unanswered": 0}
    v = check.compare(list(got), want, limits)
    assert v.numbers["max_rel_error"][0] == pytest.approx(0.01 / np.sqrt(0.125), rel=1e-6)
    assert v.numbers["rel_l2_error"][0] == pytest.approx(0.01 / 0.5, rel=1e-6)
    assert not v.correct
    assert check.compare(list(want), want, limits).correct


@pytest.mark.parametrize("got", [
    np.asarray([1.0, np.nan], np.float32),  # not finite
    np.asarray([1.0, 2.0], np.float64),  # another type
    np.asarray([1.0, 2.0, 3.0], np.float32),  # another shape
])
def test_float_measures_read_inf_where_no_difference_can_be_taken(got):
    want = np.asarray([[1.0, 2.0]], np.float32)
    v = check.compare([got], want, {"max_rel_error": 1.0, "unanswered": 0})
    assert v.numbers["max_rel_error"][0] == np.inf and not v.correct
    import json

    assert json.loads(json.dumps(v.as_json()))["max_rel_error"]["value"] == "inf"


@pytest.mark.parametrize("limits", [
    {"max_abs_error": 0.1, "unanswered": 0},  # a name compare does not know
    {"wrong_elements": 0},  # unanswered not named
    {"unanswered": 0},  # no measure of the answers
])
def test_limits_compare_does_not_know_are_an_error(limits):
    want = np.zeros((1, 2), np.float32)
    with pytest.raises(ValueError, match="check.limits"):
        check.compare([want[0]], want, limits)
