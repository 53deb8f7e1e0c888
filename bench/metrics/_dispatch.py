"""Shared by the ``dispatch_ms.*`` readers: the window's total host time
inside ``run_many`` over the number of calls."""


def mean_call_ms(run):
    starts, ends = run.calls.start, run.calls.end
    if not starts:
        return None
    return 1e3 * sum(e - s for s, e in zip(starts, ends)) / len(starts)
