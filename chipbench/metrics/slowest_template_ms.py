"""slowest_template_ms: the median latency of the template slowest at its
median, over the queries of it sent in the window (LUBM Q9 today)."""
import numpy as np


def read(run):
    by_template = {}
    for r in run.requests:
        if r.status == "ok":
            by_template.setdefault(r.query.template, []).append(r.latency_s)
    if not by_template:
        return None
    return max(float(np.median(v)) for v in by_template.values()) * 1e3
