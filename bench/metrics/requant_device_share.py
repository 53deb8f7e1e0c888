"""Kernels: the share of the batched int8 dense rows (the attention
context) whose requantize ran on the device, in the quantized GEMM
kernel's epilogue — the program's counters ``bmm_requant_device_rows``
over ``bmm_requant_device_rows`` plus ``bmm_requant_host_rows`` (rows
requantized on the host after a batched GEMM) over the window, in
percent.  Cells with attention; moves ``throughput``."""


def read(run):
    c = run.counters
    if not c:
        return None
    device, host = c.get("bmm_requant_device_rows", 0), c.get("bmm_requant_host_rows", 0)
    if not device + host:
        return None
    return 100.0 * device / (device + host)
