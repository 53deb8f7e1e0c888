"""Kernels: the share of the SiLU·mul rows that ran on the device, in the
fused ``swiglu`` step after the gate|up grouped GEMM — the program's
counters ``swiglu_device_rows`` over ``swiglu_device_rows`` plus
``swiglu_host_rows`` (rows the host ``silu_mul`` step computed) over the
window, in percent.  Cells with routed SwiGLU experts; moves
``throughput``."""


def read(run):
    c = run.counters
    if not c:
        return None
    device, host = c.get("swiglu_device_rows", 0), c.get("swiglu_host_rows", 0)
    if not device + host:
        return None
    return 100.0 * device / (device + host)
