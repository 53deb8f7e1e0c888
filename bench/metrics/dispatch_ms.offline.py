"""Plan: mean host time of one closed-loop ``run_many`` call.  Closed-loop
cells; moves ``throughput``."""

from bench.metrics._dispatch import mean_call_ms


def read(run):
    return mean_call_ms(run) if run.loop == "closed" else None
