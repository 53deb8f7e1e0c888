"""Shared by the readers of the program's own spans: the window's
``repro.*`` spans (``bench/program.py``), read once per traced run."""

from bench import program


def h2d_bytes_per_sample(run):
    """Bytes the program's uploads moved in the window (the ``bytes`` its
    ``repro.h2d`` spans carry) over the samples the window answered."""
    p = program.of_run(run)
    if p is None or program.EXECUTE not in p.program_spans or run.samples == 0:
        return None
    return p.bytes.get("repro.h2d", 0) / run.samples
