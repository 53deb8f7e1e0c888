"""Samples answered per second: every sample the window's calls completed,
over the time from the first call's start to the last call's end.  A
sample is one ToyCar window or one MusicGen sequence.  Host clock;
closed-loop cells only."""


def read(run):
    if run.loop != "closed" or run.window_s <= 0 or run.samples == 0:
        return None
    return run.samples / run.window_s
