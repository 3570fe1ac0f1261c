"""query_p65_ms: 65th percentile of the latency, submit until the answers
are on the host, over every query sent in the window (numpy's linear
interpolation).  The 31 queries a 44 s window sends leave 11 beyond it,
at least the 10 a tail needs."""
import numpy as np


def read(run):
    lat = [r.latency_s for r in run.requests if r.status == "ok"]
    return float(np.percentile(lat, 65)) * 1e3 if lat else None
