"""device_idle_share: percent of the traced window in which the device ran
no operation."""


def read(run):
    t = run.trace
    if t is None or t.busy_s is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
