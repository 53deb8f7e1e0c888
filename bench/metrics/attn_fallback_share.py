"""Kernels: the share of the attention-score rows that the device epilogue
handed back to the host chain because its rounding guard flagged them —
the program's counters ``attn_fallback_rows`` over ``attn_epilogue_rows``
over the window, in percent.  Cells with a fused attention epilogue; moves
``throughput``."""


def read(run):
    c = run.counters
    if not c or not c.get("attn_epilogue_rows"):
        return None
    return 100.0 * c["attn_fallback_rows"] / c["attn_epilogue_rows"]
