"""device_ms_per_query: device busy time in the traced window (union of
the device's operation intervals) over the queries answered in it."""


def read(run):
    t = run.trace
    if t is None or t.busy_s is None or not run.completed:
        return None
    return t.busy_s * 1e3 / len(run.completed)
