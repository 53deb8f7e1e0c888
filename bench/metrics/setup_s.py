"""Set-up: process start to the first timed request (imports, weights,
repro.compile, warm-up of the cell's own shapes, inputs).  Host clock."""


def read(run):
    return run.setup_s
