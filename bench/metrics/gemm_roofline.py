"""Kernels: the least time the chip could take for the GEMM work the
traced calls issued, over the device time of the events the trace
reduction classes as the scheduled GEMM kernel, in percent.  The least
time is max(operations / int8 peak, bytes / HBM bandwidth) per GEMM,
counted from the configuration's shapes (``bench/counting.py``), so the
same work reads the same whatever kernel does it.  Closed-loop cells;
moves ``throughput``."""

from bench import counting


def read(run):
    t = run.trace
    if run.loop != "closed" or t is None or t.gemm_s <= 0 or not run.calls.samples or not run.peaks:
        return None
    p = run.peaks
    ideal = sum(
        counting.ideal_s(run.call_gemms(n), p["int8_ops_per_s"], p["hbm_bytes_per_s"])
        for n in run.calls.samples
    )
    return 100.0 * ideal / t.gemm_s
