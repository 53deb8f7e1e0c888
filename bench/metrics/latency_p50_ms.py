"""Median latency over every request of the window, each timed from its
due time to its answer (an unanswered request counts the whole wait).
Host clock; open-loop cells only."""

from bench.record import percentile


def read(run):
    if run.latencies_s is None or len(run.latencies_s) == 0:
        return None
    return percentile(run.latencies_s, 50) * 1e3
