"""Program spans and transfer counters.

Spans are ``jax.profiler.TraceAnnotation``s.  Under a profiler
(``jax.profiler.trace``) they land in the profiler's own trace, on the
clock of the device's op line, so a reduction of the trace can put device
idle time on the program step that was running.  With no profiler running,
``span`` constructs nothing and costs one ``is_enabled()`` check.

Counters are process-wide integers (``COUNTERS``), exact when several
threads run plans at once.  ``snapshot()`` returns them; read a stretch of
work as the difference of two snapshots (``since``), as with a Prometheus
counter.

Span names (docs/architecture.md, "Observability"):

* ``repro.serve.collect`` / ``repro.serve.dispatch`` — the MicroBatcher
  worker gathering a batch / running it;
* ``repro.batch.pack`` / ``repro.batch.unpack`` — BatchedModule packing
  and padding a bucket, and slicing its rows back out;
* ``repro.plan.execute`` — one ExecutionPlan execution on one feed set;
* ``repro.host.<op>`` / ``repro.accel.<op>`` — one plan step of that lane;
* ``repro.h2d`` / ``repro.launch`` / ``repro.d2h`` — inside a Pallas
  step: one upload of a host array, one kernel enqueue, one result sync.
  ``repro.h2d`` and ``repro.d2h`` carry the bytes moved as a ``bytes`` stat;
* ``repro.accel.attn_scores`` — the step that runs an attention-score
  epilogue (dequantize, mask, softmax, quantize) on the device after its
  scores GEMM; ``repro.host.softmax_fallback`` inside it — the host chain
  recomputing the rows its rounding guard flagged;
* ``repro.accel.generalized_grouped_dense`` — one grouped expert GEMM (all
  experts, one launch); ``repro.host.rms_norm``, ``.rope``, ``.silu_mul``,
  ``.moe_route``, ``.moe_group_sizes``, ``.moe_dispatch``,
  ``.moe_combine`` — the host steps of a routed-expert block;
* ``repro.accel.swiglu`` — the step that runs a gate|up grouped expert
  GEMM and the SiLU·mul after it on the device, exact against the host's
  table (``lowering._swiglu_step``).

Counters: ``h2d_transfers`` / ``h2d_bytes``, ``d2h_syncs`` / ``d2h_bytes``,
``kernel_launches``; ``attn_epilogue_rows`` — rows of scores the device
epilogue ran over; ``attn_fallback_rows`` — those of them the host chain
recomputed; ``expert_rows`` — token-expert rows routed into grouped
expert GEMMs; ``expert_pad_rows`` — rows those GEMMs computed beyond the
routed ones, from row-tile padding at expert boundaries;
``expert_rows_peak`` — per grouped GEMM, the number of experts times the
busiest expert's rows (``expert_rows_peak / expert_rows`` is the routing's
imbalance: 1 when every expert gets as many rows); ``swiglu_device_rows``
— rows of SiLU·mul the fused ``swiglu`` step computed on the device;
``swiglu_host_rows`` — rows the host ``silu_mul`` step computed.
"""

from __future__ import annotations

import contextlib
import threading

from jax.profiler import TraceAnnotation

#: the counters ``snapshot`` returns
COUNTERS = (
    "h2d_transfers",
    "h2d_bytes",
    "d2h_syncs",
    "d2h_bytes",
    "kernel_launches",
    "attn_epilogue_rows",
    "attn_fallback_rows",
    "expert_rows",
    "expert_pad_rows",
    "expert_rows_peak",
    "swiglu_device_rows",
    "swiglu_host_rows",
)

_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_counts = dict.fromkeys(COUNTERS, 0)


def enabled() -> bool:
    """Whether a profiler is recording spans."""
    return TraceAnnotation.is_enabled()


def span(name: str):
    """A span named ``name`` while a profiler runs; a shared no-op context
    otherwise."""
    if TraceAnnotation.is_enabled():
        return TraceAnnotation(name)
    return _NULL


def spanned(name: str, fn):
    """``fn`` inside an unconditional span: for paths taken only once
    ``enabled()`` was checked."""

    def run(*args):
        with TraceAnnotation(name):
            return fn(*args)

    return run


def count_h2d(nbytes: int) -> None:
    with _lock:
        _counts["h2d_transfers"] += 1
        _counts["h2d_bytes"] += nbytes


def count_d2h(nbytes: int) -> None:
    with _lock:
        _counts["d2h_syncs"] += 1
        _counts["d2h_bytes"] += nbytes


def count_launch() -> None:
    with _lock:
        _counts["kernel_launches"] += 1


def count_attn_rows(rows: int, fallback: int) -> None:
    with _lock:
        _counts["attn_epilogue_rows"] += rows
        _counts["attn_fallback_rows"] += fallback


def count_expert_rows(rows: int, pad: int, peak: int) -> None:
    with _lock:
        _counts["expert_rows"] += rows
        _counts["expert_pad_rows"] += pad
        _counts["expert_rows_peak"] += peak


def count_swiglu_rows(device: int, host: int) -> None:
    with _lock:
        _counts["swiglu_device_rows"] += device
        _counts["swiglu_host_rows"] += host


def snapshot() -> dict[str, int]:
    """Every counter's value since the process started."""
    with _lock:
        return dict(_counts)


def since(before: dict[str, int]) -> dict[str, int]:
    """What each counter has counted since the snapshot ``before``."""
    now = snapshot()
    return {k: now[k] - before.get(k, 0) for k in COUNTERS}
