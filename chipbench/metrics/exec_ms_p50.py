"""exec_ms_p50: median service time (``Outcome.exec_s``: dequeue until the
answer) of the queries sent in the window."""
import numpy as np


def read(run):
    e = [r.exec_s for r in run.requests if r.status == "ok"]
    return float(np.median(e)) * 1e3 if e else None
