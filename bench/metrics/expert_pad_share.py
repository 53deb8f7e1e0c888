"""Kernels: the share of the rows the grouped expert GEMMs computed that
no token was routed to — row-tile padding at expert boundaries — the
program's counters ``expert_pad_rows`` over ``expert_rows`` plus
``expert_pad_rows`` over the window, in percent.  Cells with routed
experts; moves ``throughput``."""


def read(run):
    c = run.counters
    if not c or not c.get("expert_rows"):
        return None
    return 100.0 * c["expert_pad_rows"] / (c["expert_rows"] + c["expert_pad_rows"])
