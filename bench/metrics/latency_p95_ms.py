"""95th percentile of the latencies ``latency_p50_ms`` reads.  Host clock;
open-loop cells only."""

from bench.record import percentile


def read(run):
    if run.latencies_s is None or len(run.latencies_s) == 0:
        return None
    return percentile(run.latencies_s, 95) * 1e3
