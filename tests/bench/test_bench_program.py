"""The program's own spans in a traced window (``bench/program.py``) and
the metrics that read them: span tables and device idle time by program
span on synthetic intervals and on the recorded TPU trace (which predates
the spans).  ``tests/test_trace.py`` reduces CPU captures of compiled
modules with the same code."""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import generator, program, spec, tracing  # noqa: E402
from bench.record import Run  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "toycar_clip.xplane.pb"
READERS = [
    "h2d_bytes_per_sample.stream", "h2d_bytes_per_sample.offline", "d2h_share.stream",
    "host_op_share.offline", "batcher_busy_share",
]


def _spans(*rows):
    """(start_ns, end_ns, name[, bytes]) rows as one thread's sorted line."""
    return sorted((s, e, n, b[0] if b else 0) for s, e, n, *b in rows)


def test_span_table_counts_totals_and_longest():
    worker = _spans(
        (0, 100, "repro.plan.execute"), (10, 30, "repro.h2d", 640), (30, 40, "repro.launch"),
        (40, 90, "repro.d2h", 16), (200, 260, "repro.plan.execute"), (210, 220, "repro.h2d", 64),
    )
    other = _spans((50, 70, "repro.h2d", 6))
    spans, moved = program.summarize([worker, other])
    assert spans["repro.plan.execute"] == pytest.approx([2, 160e-9, 100e-9])
    assert spans["repro.h2d"] == pytest.approx([3, 50e-9, 20e-9])
    assert moved == {"repro.h2d": 710, "repro.d2h": 16}
    assert list(spans) == sorted(spans)


def test_idle_goes_to_the_innermost_program_span_on_any_thread():
    # device busy [0, 10], [50, 60], [90, 95] in a 120 ns window: gaps
    # [10, 50] (mid 30), [60, 90] (mid 75), [95, 120] (mid 107.5)
    busy = np.asarray([[0, 10], [50, 60], [90, 95]], dtype=float)
    worker = _spans((0, 80, "repro.plan.execute"), (20, 40, "repro.d2h"), (62, 70, "repro.h2d"))
    late_thread = _spans((100, 115, "repro.serve.collect"), (101, 101, "repro.launch"))
    idle = program.idle_by_span([worker, late_thread], busy, 120.0)
    assert idle == [["repro.d2h", 40e-9], ["repro.plan.execute", 30e-9],
                    ["repro.serve.collect", 25e-9]]
    assert program.idle_by_span([], busy, 120.0) == [[program.NO_SPAN, 95e-9]]
    assert program.idle_by_span([worker], None, 120.0) == []


def test_shares_of_plan_execution():
    p = program.ProgramTrace(2.0, {
        "repro.plan.execute": [4, 1.0, 0.3], "repro.host.softmax": [4, 0.5, 0.2],
        "repro.host.add": [4, 0.25, 0.1], "repro.d2h": [8, 0.1, 0.02],
    })
    assert p.share_of_execute("repro.host.") == pytest.approx(75.0)
    assert p.share_of_execute("repro.d2h") == pytest.approx(10.0)
    assert program.ProgramTrace(2.0).share_of_execute("repro.d2h") is None


def test_recorded_trace_has_no_program_spans():
    """The recorded TPU trace predates the program's spans: all its idle
    time is under no program span, the same total ``bench.tracing`` puts
    on its host spans."""
    s = tracing.reduce_trace(RECORDED)
    p = program.reduce_program(RECORDED)
    assert p.program_spans == {} and p.bytes == {}
    assert p.window_s == pytest.approx(s.window_s)
    assert [name for name, _ in p.idle_by_program_span] == [program.NO_SPAN]
    assert p.idle_by_program_span[0][1] == pytest.approx(s.window_s - s.busy_s)


def _run(loop: str, samples: int, traced: bool = True) -> Run:
    calls = generator.Calls()
    calls.add(0.0, 0.5, samples)
    return Run(
        loop, {}, {}, None, (1, 640), {}, 1.0, 0.5, 0.25, window_s=2.0, samples=samples,
        calls=calls, trace=SimpleNamespace(window_s=2.0) if traced else None,
    )


SYNTHETIC = program.ProgramTrace(
    4.0,
    {
        "repro.serve.dispatch": [10, 3.0, 0.4], "repro.plan.execute": [12, 2.0, 0.2],
        "repro.accel.generalized_dense": [120, 1.5, 0.02], "repro.d2h": [120, 1.2, 0.01],
        "repro.host.softmax": [24, 0.4, 0.02], "repro.h2d": [360, 0.2, 0.001],
    },
    [["repro.d2h", 2.5]],
    {"repro.h2d": 297_632 * 12, "repro.d2h": 16 * 1672},
)


@pytest.mark.parametrize(
    "name, loop, want",
    [
        ("h2d_bytes_per_sample.stream", "open", 297_632 * 12 / 160),
        ("h2d_bytes_per_sample.offline", "closed", 297_632 * 12 / 160),
        ("d2h_share.stream", "open", 60.0),
        ("host_op_share.offline", "closed", 20.0),
        ("batcher_busy_share", "open", 75.0),
    ],
)
def test_program_readers(monkeypatch, name, loop, want):
    read = spec.metric_reader(name)
    monkeypatch.setattr(program, "of_run", lambda run: SYNTHETIC if run.trace else None)
    assert read(_run(loop, 160)) == pytest.approx(want)
    other = "closed" if loop == "open" else "open"
    assert read(_run(other, 160)) is None
    assert read(_run(loop, 160, traced=False)) is None
    # a trace without the program's spans (a program that predates them)
    monkeypatch.setattr(program, "of_run", lambda run: program.ProgramTrace(4.0))
    assert read(_run(loop, 160)) is None


def test_no_uploads_in_a_traced_plan_read_zero(monkeypatch):
    p = program.ProgramTrace(1.0, {"repro.plan.execute": [3, 0.1, 0.04]})
    monkeypatch.setattr(program, "of_run", lambda run: p)
    assert spec.metric_reader("h2d_bytes_per_sample.offline")(_run("closed", 6)) == 0.0


def test_untraced_or_missing_trace_reads_nothing(monkeypatch, tmp_path):
    from bench import run as harness

    monkeypatch.setattr(harness, "CACHE", tmp_path)
    assert program.of_run(_run("open", 4, traced=False)) is None
    assert program.of_run(_run("open", 4)) is None  # no trace under the cache
    assert all(spec.metric_reader(m)(_run(loop, 4)) is None
               for m in READERS for loop in ("open", "closed"))
