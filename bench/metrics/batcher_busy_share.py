"""Serving: the share of the traced window the MicroBatcher's worker spent
dispatching — its ``repro.serve.dispatch`` spans over the window, in
percent; near 100 the server is at or past its capacity.  Open-loop cells;
moves ``latency_p50_ms``."""

from bench import program


def read(run):
    p = program.of_run(run)
    if p is None or run.loop != "open" or p.window_s <= 0:
        return None
    if "repro.serve.dispatch" not in p.program_spans:
        return None
    return 100.0 * p.total_s("repro.serve.dispatch") / p.window_s
