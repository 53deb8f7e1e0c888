"""Shared by the ``device_idle_share.*`` readers: one minus the union of
device op intervals over the traced window, in percent."""


def idle_share(run):
    t = run.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
