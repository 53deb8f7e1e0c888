"""Plan: bytes uploaded to the device per sample, from the ``bytes`` the
program's ``repro.h2d`` spans carry in the traced window.  Closed-loop
cells; moves ``throughput``."""

from bench.metrics._program import h2d_bytes_per_sample


def read(run):
    return h2d_bytes_per_sample(run) if run.loop == "closed" else None
