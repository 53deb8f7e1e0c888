"""Plan: bytes uploaded to the device per answered request, from the
``bytes`` the program's ``repro.h2d`` spans carry in the traced window
(weights, biases and activations of every Pallas step).  Open-loop cells;
moves ``latency_p50_ms``."""

from bench.metrics._program import h2d_bytes_per_sample


def read(run):
    return h2d_bytes_per_sample(run) if run.loop == "open" else None
