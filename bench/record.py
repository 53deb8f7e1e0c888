"""What a run measured, as the metric readers see it.

Every reader in ``bench/metrics/<name>.py`` is ``read(run: Run)`` and
returns a number, or None where the run holds nothing for it to read
(the harness then leaves the metric out of the line).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench import counting


def percentile(values, q: float) -> float:
    """Nearest-rank percentile over all values: the smallest value with at
    least ``q`` percent of the values at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    rank = int(np.ceil(q / 100.0 * v.size))
    return float(v[max(rank, 1) - 1])


@dataclass
class Run:
    loop: str  # "open" or "closed"
    config: dict
    traffic: dict
    model: object  # the configuration's module (GEMM list)
    sample_shape: tuple
    peaks: dict
    setup_s: float
    compile_s: float
    warm_s: float
    window_s: float  # host seconds of the measured (or traced) window
    samples: int  # samples answered in the window
    calls: object  # generator.Calls
    latencies_s: np.ndarray | None = None  # open loop: answer - due, every request
    queue_waits_s: np.ndarray | None = None  # open loop: dispatch start - due
    batches: tuple | None = None  # open loop: (requests, dispatches, max_batch)
    trace: object | None = None  # tracing.TraceSummary
    counters: dict | None = None  # repro.core.trace counters: the window's counts

    def call_gemms(self, n: int) -> list[counting.Gemm]:
        return self.model.gemms(self.config, self.sample_shape, n)

    def ops_per_sample(self) -> int:
        return counting.total_ops(self.call_gemms(1))
