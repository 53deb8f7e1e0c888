"""Plan: mean host time of one ``run_many`` the MicroBatcher issued
(bucket packing, every plan step, unpacking).  Open-loop cells; moves
``latency_p50_ms``."""

from bench.metrics._dispatch import mean_call_ms


def read(run):
    return mean_call_ms(run) if run.loop == "open" else None
