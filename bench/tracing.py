"""The profiler trace of a window, and its reduction to device numbers.

``capture(dir)`` runs JAX's profiler with the Python tracer off (the
harness's own spans and JAX's dispatch events stay on the host lines).
``reduce_trace(path)`` reads the ``.xplane.pb`` with JAX alone and gives:

* ``window_s`` — the traced window, from the profile's own start and stop;
* ``busy_s`` — the union of the intervals in which an operation ran on a
  chip's op line, averaged over the chips;
* ``gemm_s`` — the device time of the events classed as the scheduled
  GEMM kernel (``is_gemm_kernel``);
* ``kernel_s`` — device time per Mosaic custom call, keyed by its
  instruction's name without the numeric suffix (``%qmatmul``,
  ``kernel_name``): a kernel's roofline share is one reader of it;
* ``device_ops`` — the ten operations that took most device time;
* ``idle_gaps`` — device idle time, summed by what the host was doing in
  each gap: the innermost host span that covers the gap's midpoint.
"""

from __future__ import annotations

import contextlib
import glob
import shutil
import warnings
from dataclasses import dataclass
from pathlib import Path

import numpy as np

#: the line of a TPU plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: the scheduled Pallas GEMM (repro.kernels.gemm.scheduled_gemm, also behind
#: qgemm) is a Mosaic custom call named after its jitted wrapper in
#: repro.kernels.ops, ``matmul`` or ``qmatmul``: on a TPU v5e its op event
#: reads ``%qmatmul.1 = s8[16,128]{...} custom-call(...),
#: custom_call_target="tpu_custom_call", ...``
GEMM_KERNELS = ("%matmul", "%qmatmul")
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
TOP = 10


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    gemm_s: float
    kernel_s: dict  # kernel_name -> device seconds
    n_device_events: int
    device_ops: list
    idle_gaps: list


@contextlib.contextmanager
def capture(trace_dir: Path):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def newest_xplane(trace_dir: Path) -> Path:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


def kernel_name(name: str) -> str | None:
    """The instruction of an op event that is a Mosaic custom call, without
    its numeric suffix (``%qmatmul``); None for any other op."""
    return name.split(" = ", 1)[0].split(".", 1)[0] if CUSTOM_CALL in name else None


def is_gemm_kernel(name: str) -> bool:
    """An op event of the scheduled GEMM kernel: a Mosaic custom call whose
    instruction is named after one of the GEMM wrappers."""
    return kernel_name(name) in GEMM_KERNELS


def op_key(name: str) -> str:
    """An op event's name without its layouts and operands: the
    instruction and its result type (``%qmatmul.1 = s8[16,128]``)."""
    return name.split("{", 1)[0].strip()


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge [start, end] rows (sorted by start) into disjoint intervals."""
    if len(intervals) == 0:
        return intervals
    merged = [list(intervals[0])]
    for s, e in intervals[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return np.asarray(merged)


def _host_spans(planes):
    """Per host thread: [(start, end, name)] sorted by start."""
    lines = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in line.events
                if e.duration_ns > 0
            ]
            if spans:
                spans.sort()
                lines.append(spans)
    return lines


def _innermost_at(lines, times: np.ndarray) -> list[str]:
    """For each time (sorted), the name of the innermost host span that
    covers it on any thread (the one that started last), else "no host
    span"."""
    best = [(-1.0, "no host span")] * len(times)
    for spans in lines:
        stack: list = []
        k = 0
        for ti, t in enumerate(times):
            while k < len(spans) and spans[k][0] <= t:
                stack.append(spans[k])
                k += 1
            while stack and stack[-1][1] < t:
                stack.pop()
            # spans nest per thread; an ended outer span under a live inner
            # one is dropped once the inner one ends
            live = [s for s in stack if s[1] >= t]
            stack = live
            if live and live[-1][0] > best[ti][0]:
                best[ti] = (live[-1][0], live[-1][2])
    return [name for _, name in best]


def reduce_trace(path: Path) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = list(data.planes)
    start_ns = stop_ns = None
    for plane in planes:
        with warnings.catch_warnings():  # pybind's plane_stats lacks __module__
            warnings.simplefilter("ignore", DeprecationWarning)
            stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start_ns, stop_ns = stats["profile_start_time"], stats["profile_stop_time"]
    busy, gemm_ns, n_events = [], 0.0, 0
    per_op: dict[str, float] = {}
    per_kernel: dict[str, float] = {}
    first_chip_busy = None
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                n_events += 1
                if is_gemm_kernel(e.name):
                    gemm_ns += e.duration_ns
                kernel = kernel_name(e.name)
                if kernel is not None:
                    per_kernel[kernel] = per_kernel.get(kernel, 0.0) + e.duration_ns
                key = op_key(e.name)
                per_op[key] = per_op.get(key, 0.0) + e.duration_ns
                intervals.append((e.start_ns, e.start_ns + e.duration_ns))
        if not intervals:
            continue
        merged = _union(np.asarray(sorted(intervals), dtype=np.float64))
        busy.append(float(np.sum(merged[:, 1] - merged[:, 0])))
        if first_chip_busy is None:
            first_chip_busy = merged
    if start_ns is not None:
        window_ns = float(stop_ns - start_ns)
    elif first_chip_busy is not None:
        window_ns = float(first_chip_busy[-1, 1] - first_chip_busy[0, 0])
    else:
        window_ns = 0.0
    gaps = []
    if first_chip_busy is not None:
        ends = np.concatenate([[0.0], first_chip_busy[:, 1]])
        starts = np.concatenate([first_chip_busy[:, 0], [window_ns]])
        gap_len = starts - ends
        keep = gap_len > 0
        mids = (ends[keep] + starts[keep]) / 2
        names = _innermost_at(_host_spans(planes), mids)
        by_name: dict[str, float] = {}
        for name, ns in zip(names, gap_len[keep]):
            by_name[name] = by_name.get(name, 0.0) + float(ns)
        gaps = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=window_ns / 1e9,
        busy_s=(sum(busy) / len(busy) / 1e9) if busy else 0.0,
        gemm_s=gemm_ns / 1e9,
        kernel_s={k: ns / 1e9 for k, ns in sorted(per_kernel.items())},
        n_device_events=n_events,
        device_ops=[[name, ns / 1e9] for name, ns in ops],
        idle_gaps=[[name, ns / 1e9] for name, ns in gaps],
    )
