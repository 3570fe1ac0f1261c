#!/usr/bin/env python
"""Render a trace export as per-request waterfalls + a slow-span table.

Reads the JSON file ``repro.obs.export.export_traces`` writes (the
serving bench's ``REPRO_TRACE_EXPORT`` hook) and prints:

  * a text waterfall per trace — spans indented by parent, each with a
    bar positioned in the request's [t0, t1] window, duration, and the
    attrs that explain the shape (pin path, stale degradation, retry
    attempt, shard dispatch path),
  * a top-N table of the slowest spans across every trace, the place to
    look first when a p99 regresses.

Usage:
    PYTHONPATH=src python scripts/trace_report.py traces.json
    PYTHONPATH=src python scripts/trace_report.py traces.json \
        --top 20 --max-traces 5 --slowest

``--slowest`` orders the waterfall section by root-span duration
(descending) instead of submission order, so the traces shown are the
requests worth reading.
"""
from __future__ import annotations

import argparse
import json
import sys

BAR_WIDTH = 40


def _fmt_attrs(span: dict) -> str:
    attrs = dict(span.get("attrs", {}))
    parts = [f"{k}={v}" for k, v in attrs.items()]
    parts += [f"!{ev['name']}" for ev in span.get("events", [])]
    return (" [" + " ".join(parts) + "]") if parts else ""


def _children(spans: list) -> dict:
    by_parent: dict = {}
    for s in spans:
        by_parent.setdefault(s["parent_id"], []).append(s)
    for kids in by_parent.values():
        kids.sort(key=lambda s: (s["t0"], s["span_id"]))
    return by_parent


def waterfall(trace: dict, out=sys.stdout) -> None:
    spans = trace["spans"]
    roots = [s for s in spans if s["parent_id"] == -1]
    if not roots:
        return
    root = roots[0]
    t0, t1 = root["t0"], max(s["t1"] for s in spans)
    window = max(t1 - t0, 1e-9)
    by_parent = _children(spans)
    out.write(f"{trace['trace_id']}  "
              f"({(root['t1'] - root['t0']) * 1e3:.2f} ms)"
              f"{_fmt_attrs(root)}\n")

    def emit(span: dict, depth: int) -> None:
        lo = int((span["t0"] - t0) / window * BAR_WIDTH)
        hi = max(int((span["t1"] - t0) / window * BAR_WIDTH), lo + 1)
        bar = " " * lo + "#" * (hi - lo) + " " * (BAR_WIDTH - hi)
        dur_ms = (span["t1"] - span["t0"]) * 1e3
        label = "  " * depth + span["name"]
        out.write(f"  |{bar}| {dur_ms:9.3f} ms  "
                  f"{label}{_fmt_attrs(span)}\n")
        for kid in by_parent.get(span["span_id"], []):
            emit(kid, depth + 1)

    emit(root, 0)
    out.write("\n")


def slow_spans(traces: list, top: int) -> list:
    """[(duration_s, trace_id, span)] of the ``top`` slowest spans."""
    rows = []
    for trace in traces:
        for s in trace["spans"]:
            rows.append((s["t1"] - s["t0"], trace["trace_id"], s))
    rows.sort(key=lambda r: -r[0])
    return rows[:top]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("file")
    ap.add_argument("--top", type=int, default=10,
                    help="rows in the slowest-spans table")
    ap.add_argument("--max-traces", type=int, default=10,
                    help="waterfalls to print (0 = none)")
    ap.add_argument("--slowest", action="store_true",
                    help="order waterfalls by root duration, not arrival")
    args = ap.parse_args(argv)

    with open(args.file) as f:
        doc = json.load(f)
    traces = [t for t in doc.get("traces", []) if t.get("spans")]
    if not traces:
        print(f"{args.file}: no traces")
        return 1

    shown = traces
    if args.slowest:
        shown = sorted(traces, key=lambda t: t["spans"][0]["t0"]
                       - t["spans"][0]["t1"])
    for trace in shown[:args.max_traces]:
        waterfall(trace)

    print(f"top {args.top} slowest spans "
          f"({len(traces)} traces, {doc.get('dropped', 0)} dropped):")
    for dur, tid, s in slow_spans(traces, args.top):
        print(f"  {dur * 1e3:9.3f} ms  {s['name']:<18} {tid}"
              f"{_fmt_attrs(s)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
