"""Kernel compile: host clock around the warm-up calls, the first of which
per shape compiles its Pallas kernels through Mosaic (or loads them from
JAX's cache).  Moves ``setup_s``."""


def read(run):
    return run.warm_s
