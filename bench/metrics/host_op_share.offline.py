"""Plan: the share of plan execution time spent in host-lane steps — the
program's ``repro.host.*`` spans over its ``repro.plan.execute`` spans, in
percent.  Closed-loop cells; moves ``throughput``."""

from bench import program


def read(run):
    p = program.of_run(run)
    return p.share_of_execute("repro.host.") if p is not None and run.loop == "closed" else None
