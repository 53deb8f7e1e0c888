"""Device: idle share of the traced window in closed-loop cells (profiler
trace).  Moves ``throughput``."""

from bench.metrics._idle import idle_share


def read(run):
    return idle_share(run) if run.loop == "closed" else None
