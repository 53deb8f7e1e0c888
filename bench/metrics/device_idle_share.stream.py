"""Device: idle share of the traced window in open-loop cells (profiler
trace).  Moves ``latency_p50_ms``."""

from bench.metrics._idle import idle_share


def read(run):
    return idle_share(run) if run.loop == "open" else None
