"""Serving: 95th percentile over every request of the time from its due
time to the start of the ``run_many`` that carried it (the harness wraps
the module it hands to MicroBatcher).  Host clock; open-loop cells only.
Moves ``latency_p50_ms``."""

import numpy as np

from bench.record import percentile


def read(run):
    if run.queue_waits_s is None:
        return None
    waits = run.queue_waits_s[~np.isnan(run.queue_waits_s)]
    return percentile(waits, 95) * 1e3 if len(waits) else None
