"""The program's own spans in a traced window, and the device idle time
put on them.

``repro.core.trace`` opens spans named ``repro.*`` inside the program (the
MicroBatcher, the bucket packing, each plan execution and step, each
upload, kernel enqueue and result sync of a Pallas step).  They land in the
profiler's trace on the device's clock.  ``reduce_program(path)`` reads the
window's ``.xplane.pb`` and gives:

* ``program_spans`` — per ``repro.*`` name: count, total seconds and
  longest seconds;
* ``idle_by_program_span`` — device idle time summed by the innermost
  ``repro.*`` span covering each gap's midpoint, on any thread (the same
  gaps as ``bench.tracing``'s ``idle_gaps``), top ``tracing.TOP``;
* ``bytes`` — per name, the sum of the ``bytes`` stat its spans carry
  (``repro.h2d`` and ``repro.d2h``: the bytes each upload and sync moved).

``of_run(run)`` gives it for a traced run, read once however many metrics
ask; None for an untraced run or where no trace is found.  A trace of a
program without these spans reduces to empty tables.

    python3 -m bench.program [trace dir]

prints both for the newest trace under the directory (default
``bench/.cache/trace``, where ``bench/run.py`` leaves the traced window).
"""

from __future__ import annotations

import functools
import json
import sys
import warnings
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from bench import tracing

PREFIX = "repro."
EXECUTE = "repro.plan.execute"
NO_SPAN = "no program span"


@dataclass
class ProgramTrace:
    window_s: float
    program_spans: dict = field(default_factory=dict)  # name -> [count, total_s, longest_s]
    idle_by_program_span: list = field(default_factory=list)  # [[name, seconds]]
    bytes: dict = field(default_factory=dict)  # name -> bytes its spans moved

    def total_s(self, prefix: str) -> float:
        """Seconds inside every span whose name starts with ``prefix``."""
        return sum(v[1] for k, v in self.program_spans.items() if k.startswith(prefix))

    def share_of_execute(self, prefix: str):
        """Percent of the time inside ``repro.plan.execute`` spent in spans
        named ``prefix...``; None where no plan execution was traced."""
        whole = self.total_s(EXECUTE)
        return 100.0 * self.total_s(prefix) / whole if whole > 0 else None


def program_lines(planes) -> list[list[tuple]]:
    """Per host thread: the ``repro.*`` spans as (start_ns, end_ns, name,
    bytes), sorted by start."""
    lines = []
    with warnings.catch_warnings():  # pybind's event_stats lacks __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                spans = []
                for e in line.events:
                    if not e.name.startswith(PREFIX):
                        continue
                    nbytes = 0
                    if e.name in ("repro.h2d", "repro.d2h"):
                        nbytes = next((int(v) for k, v in e.stats if k == "bytes"), 0)
                    spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name, nbytes))
                if spans:
                    spans.sort()
                    lines.append(spans)
    return lines


def summarize(lines) -> tuple[dict, dict]:
    """``program_spans`` and ``bytes`` of the spans in ``lines``."""
    spans: dict[str, list] = {}
    moved: dict[str, int] = {}
    for line in lines:
        for start, end, name, nbytes in line:
            s = (end - start) / 1e9
            row = spans.setdefault(name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += s
            row[2] = max(row[2], s)
            if nbytes:
                moved[name] = moved.get(name, 0) + nbytes
    return dict(sorted(spans.items())), moved


def idle_by_span(lines, busy: np.ndarray | None, window_ns: float) -> list:
    """Device idle time (the gaps between the merged ``busy`` intervals and
    the window's ends) by the innermost program span at each gap's
    midpoint, largest first."""
    if busy is None or len(busy) == 0:
        return []
    ends = np.concatenate([[0.0], busy[:, 1]])
    starts = np.concatenate([busy[:, 0], [window_ns]])
    gap = starts - ends
    keep = gap > 0
    mids = (ends[keep] + starts[keep]) / 2
    timed = [[(s, e, name) for s, e, name, _ in line if e > s] for line in lines]
    names = tracing._innermost_at([t for t in timed if t], mids)
    by_name: dict[str, float] = {}
    for name, ns in zip(names, gap[keep]):
        name = NO_SPAN if name == "no host span" else name
        by_name[name] = by_name.get(name, 0.0) + float(ns)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[: tracing.TOP]
    return [[name, ns / 1e9] for name, ns in top]


def _first_chip_busy(planes) -> np.ndarray | None:
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        intervals = [
            (e.start_ns, e.start_ns + e.duration_ns)
            for line in plane.lines
            if line.name == tracing.OPS_LINE
            for e in line.events
        ]
        if intervals:
            return tracing._union(np.asarray(sorted(intervals), dtype=np.float64))
    return None


def reduce_program(path: Path) -> ProgramTrace:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(path)).planes)
    window_ns = None
    for plane in planes:
        with warnings.catch_warnings():  # pybind's plane_stats lacks __module__
            warnings.simplefilter("ignore", DeprecationWarning)
            stats = dict(plane.stats)
        if "profile_start_time" in stats:
            window_ns = float(stats["profile_stop_time"] - stats["profile_start_time"])
    busy = _first_chip_busy(planes)
    if window_ns is None:
        window_ns = float(busy[-1, 1] - busy[0, 0]) if busy is not None else 0.0
    lines = program_lines(planes)
    spans, moved = summarize(lines)
    return ProgramTrace(window_ns / 1e9, spans, idle_by_span(lines, busy, window_ns), moved)


@functools.lru_cache(maxsize=1)
def _reduce_cached(path: str, mtime_ns: int) -> ProgramTrace:
    return reduce_program(Path(path))


def trace_dir() -> Path:
    """Where ``bench/run.py`` captures the traced window."""
    from bench import run as harness

    return harness.CACHE / "trace"


def of_run(run) -> ProgramTrace | None:
    """The program's spans in a traced run's window; None for an untraced
    run or where no trace is found."""
    if run.trace is None:
        return None
    try:
        path = tracing.newest_xplane(trace_dir())
    except FileNotFoundError:
        return None
    return _reduce_cached(str(path), path.stat().st_mtime_ns)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    p = reduce_program(tracing.newest_xplane(Path(argv[0]) if argv else trace_dir()))
    print(json.dumps({
        "window_s": p.window_s,
        "program_spans": p.program_spans,
        "idle_by_program_span": p.idle_by_program_span,
        "bytes": p.bytes,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
