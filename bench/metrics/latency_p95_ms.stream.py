"""Serving: the 95th percentile of the latencies ``latency_p50_ms`` reads,
over the traced window.  Host clock; open-loop cells only.  Per layer and
not end to end: one stall of the machine of a second or more, in about
one run of four, holds the queue up for seconds and lifts the tail of a
whole run ten- to twentyfold (PERF.md §2).  Moves ``latency_p50_ms``."""

from bench.record import percentile


def read(run):
    if run.latencies_s is None or len(run.latencies_s) == 0:
        return None
    return percentile(run.latencies_s, 95) * 1e3
