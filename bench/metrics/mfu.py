"""Model step: the model's int8 operations per sample (its GEMMs, the
attention GEMMs included, from the configuration's shapes) times the
samples the window answered, over the window's host seconds times the
chip's int8 peak, in percent.  Closed-loop cells; moves ``throughput``."""


def read(run):
    if run.loop != "closed" or run.window_s <= 0 or run.samples == 0 or not run.peaks:
        return None
    rate = run.ops_per_sample() * run.samples / run.window_s
    return 100.0 * rate / run.peaks["int8_ops_per_s"]
