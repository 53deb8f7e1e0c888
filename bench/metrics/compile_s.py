"""Front door + passes + CoSA: host clock around ``repro.compile`` (trace
of the model function, pass pipeline, scheduling of every bucket, plan
building).  Moves ``setup_s``."""


def read(run):
    return run.compile_s
