"""Kernels: the least time the chip could take for the routed-expert GEMMs
the traced calls issued, over the device time of the grouped expert
kernel, in percent.  The least time is max(operations / int8 peak, bytes /
HBM bandwidth) per GEMM, counted from the configuration's expert GEMMs
(``gemms()`` names them ``l<i>.expert_*``; ``bench/counting.py``); the
kernel time is that of the Mosaic custom calls named after the grouped
kernel (``repro.kernels.grouped``) in the trace's ``kernel_s``.  None where
the trace holds no such kernel.  Closed-loop cells; moves ``throughput``."""

from bench import counting

#: the grouped kernel's name, as its Mosaic custom calls carry it
KERNEL = "grouped_qmatmul"


def read(run):
    t = run.trace
    if run.loop != "closed" or t is None or not run.calls.samples or not run.peaks:
        return None
    kernel_s = sum(s for name, s in t.kernel_s.items() if KERNEL in name)
    if kernel_s <= 0:
        return None
    p = run.peaks
    ideal = sum(
        counting.ideal_s([g for g in run.call_gemms(n) if ".expert_" in g.name],
                         p["int8_ops_per_s"], p["hbm_bytes_per_s"])
        for n in run.calls.samples
    )
    return 100.0 * ideal / kernel_s if ideal > 0 else None
