"""The program's own span trees of a measured window, for the per-layer
readers (``chipbench/metrics/*.py``).

While a ``jax.profiler`` trace is being captured, as in a traced run
(``--trace 1``), the serving runtime records one span tree per request it
admits into ``repro.obs.trace.PROFILE_TRACER``; each span on one thread
also enters the profiler's trace under its own name.  A program without
that tracer records none, and every reader of these trees then reports
nothing.

A tree is a list of span dicts (``name``, ``span_id``, ``parent_id``,
``t0``, ``t1``, ``attrs``, ``events``), the root first, with ``t0`` and
``t1`` moved to ``time.perf_counter``'s clock (the tracer's epoch plus
its offsets): the clock of ``Run.window_open`` and ``Request.t0``.
"""
from __future__ import annotations

ROOT = "serving.request"


def answered(run) -> list:
    """The trees of the requests answered inside the window: root status
    ok, submitted at or after its open and answered by its close."""
    from repro.obs import trace

    tracer = getattr(trace, "PROFILE_TRACER", None)
    if tracer is None:
        return []
    out = []
    for tr in tracer.finished_traces():
        spans = [dict(s, t0=tracer.epoch + s["t0"], t1=tracer.epoch + s["t1"])
                 for s in tr.to_dict()["spans"]]
        root = spans[0]
        if (root["name"] == ROOT and root["attrs"].get("status") == "ok"
                and run.window_open <= root["t0"]
                and root["t1"] <= run.window_close):
            out.append(spans)
    return out


def per_query_ms(run, names) -> float | None:
    """Summed duration of the spans named in ``names`` over the answered
    requests, in ms per answered request; None where none was traced."""
    trees = answered(run)
    if not trees:
        return None
    total = sum(s["t1"] - s["t0"] for spans in trees for s in spans
                if s["name"] in names)
    return total * 1e3 / len(trees)


def union(intervals) -> list:
    """Sorted, merged [t0, t1] intervals."""
    out = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return out


def longest_uncovered(spans) -> float:
    """Longest stretch of the root's [t0, t1] that no other span covers."""
    root = spans[0]
    lo, hi = root["t0"], root["t1"]
    covered = union((max(s["t0"], lo), min(s["t1"], hi)) for s in spans[1:]
                    if s["t1"] > lo and s["t0"] < hi)
    edges = [lo] + [t for iv in covered for t in iv] + [hi]
    return max(b - a for a, b in zip(edges[::2], edges[1::2]))
