"""query_p50_ms: median latency, submit until the answers are on the
host, over every query sent in the window (numpy's linear interpolation)."""
import numpy as np


def read(run):
    lat = [r.latency_s for r in run.requests if r.status == "ok"]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
