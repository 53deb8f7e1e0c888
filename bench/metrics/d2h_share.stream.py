"""Plan: the share of plan execution time spent syncing results back to
the host — the program's ``repro.d2h`` spans over its
``repro.plan.execute`` spans, in percent.  Open-loop cells; moves
``latency_p50_ms``."""

from bench import program


def read(run):
    p = program.of_run(run)
    return p.share_of_execute("repro.d2h") if p is not None and run.loop == "open" else None
