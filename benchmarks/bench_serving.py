"""Serving-runtime latency under concurrent reads + background updates.

Drives the snapshot-isolated request runtime (serving/runtime.py) over a
LUBM store with CLOSED-LOOP clients — each client thread issues its next
request only after the previous outcome lands, so the reported p50/p99 is
service latency (pin + pinned-plan execution + any fresh snapshot
capture), not open-loop queue depth:

    serving/read_only        4 clients x Q1-Q4, no writer — pins are all
                             fast-path reuses of the published snapshot
    serving/mixed_workload   the same read stream racing a writer thread
                             that streams 64-row insert batches (each one
                             bumping the version and republishing), so
                             reads keep paying fresh snapshot captures;
                             also reports reader and writer throughput
    serving/batched_read     burst arrivals (16 clients submitting
                             back-to-back) through the micro-batching
                             scheduler — same-signature requests coalesce
                             into ONE vmapped executable per batch window;
                             reports p50/p99, throughput and the mean
                             batch occupancy read from the
                             ``serving/batch_size`` histogram
    serving/batched_speedup  pass/fail row: batched throughput must reach
                             ``BATCHED_SPEEDUP_GATE``x the read_only
                             baseline at batch occupancy >= 4
    serving/mixed_slo        pass/fail row gated by scripts/bench_diff.py:
                             at this baseline load NOTHING sheds, NOTHING
                             misses its deadline, and every request is ok
                             — admission control must be invisible until
                             overload
    serving/obs_overhead     TOTAL telemetry cost per request — tracing
                             plus the control plane (one rollup tick over
                             the stock SLO set and one resource-ledger
                             sample, amortized across the requests a
                             default 0.25 s tick interval admits at the
                             measured throughput) — as a fraction of the
                             untraced mean latency; must stay under
                             ``gate_max_pct`` (3%) or bench_diff fails
                             the build.  The cost is CALIBRATED, not
                             A/B'd: per-request latency on shared CPU
                             runners swings +/-10% between back-to-back
                             identical requests (measured), so a
                             wall-clock traced-vs-untraced diff cannot
                             resolve a 3% budget — instead the bench
                             times the exact span lifecycle a real
                             served trace performs (same span count as
                             the traced run's median trace, best-of-3)
                             plus the exact tick/sample the rollup
                             thread performs, and divides by the
                             measured untraced mean.  The raw A/B delta
                             is kept as an informational
                             ``ab_overhead_pct`` field

Every latency figure is read back from the runtime's
:class:`~repro.obs.metrics.MetricsRegistry` (``serving/latency_s`` /
``serving/queue_s`` / ``serving/exec_s`` histograms), not recomputed from
the outcome list — the BENCH rows exercise the same observability surface
operators would read.  ``REPRO_TRACE_EXPORT`` dumps the traced run's
spans; ``REPRO_METRICS_EXPORT`` writes an aggregated fleet-schema
snapshot (the serving registry and the process-global engine registry,
ledger gauges included, merged as two labelled members) — both files
are validated by scripts/check_traces.py and the latter renders through
scripts/fleet_report.py in the CI obs smoke leg.

A short unmeasured mixed warmup epoch runs first so the delta-bucket plan
compilations (pow2 capacity transitions) mostly land outside the measured
window (the registry's ``window_summary`` subtracts the warmup's
histogram state).  Writes ``BENCH_serving.json`` for the CI bench-diff
gate.
"""
from __future__ import annotations

import json
import os
import threading
import time

#: serving/obs_overhead must stay under this (scripts/bench_diff.py gates
#: any row that carries a ``gate_max_pct`` field).
OBS_OVERHEAD_GATE_PCT = 3.0

#: serving/batched_read must beat serving/read_only by this throughput
#: factor (at batch occupancy >= 4) or bench_diff fails the build.
BATCHED_SPEEDUP_GATE = 3.0


def _closed_loop(rt, queries, n_clients: int, per_client: int):
    """n_clients threads, each serving its next request only after the
    last one resolved — latency reflects service time, not queue depth."""
    outs_by_client = [[] for _ in range(n_clients)]

    def client(c: int):
        for i in range(per_client):
            q = queries[(c + i * n_clients) % len(queries)]
            outs_by_client[c].append(rt.serve(q, deadline_s=30.0))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return [o for outs in outs_by_client for o in outs], wall


def _tracer_cost_s(n_spans: int, iters: int = 200) -> float:
    """Measured wall cost of one traced request's full span lifecycle:
    trace mint, root + (n_spans - 1) child spans with attrs, context
    activation, finish into the bounded ring.  Deterministic Python work
    — repeatable to a few percent where wall-clock A/B is not."""
    from repro.obs.trace import Tracer, activate, span

    cal = Tracer(max_traces=8)
    t0 = time.perf_counter()
    for _ in range(iters):
        tr = cal.new_trace("cal")
        root = cal.start_root(tr, "serving.request", n_patterns=3,
                              mode="default")
        with activate(root):
            for _ in range(max(n_spans - 1, 0)):
                with span("s", attempt=0) as sp:
                    sp.set_attr(version=0)
        cal.finish_trace(tr)
    return (time.perf_counter() - t0) / iters


def _rollup_cost_s(registry, ledger, iters: int = 50):
    """Measured wall cost of (one rollup tick, one ledger sample).

    The tick runs on a registry carrying the bench's real instrument
    cardinality (latency histograms, outcome counters) with the stock SLO
    set attached, so collection + rate gauges + burn-rate evaluation are
    all priced; the ledger sample walks whatever owners the bench
    registered.  Deterministic Python work, like :func:`_tracer_cost_s`.
    """
    from repro.obs.slo import (SLOMonitor, TelemetryRollup,
                               default_serving_slos)

    mon = SLOMonitor(default_serving_slos(), registry=registry)
    roll = TelemetryRollup(registry, monitor=mon)
    roll.tick()  # baseline point so measured ticks do the full rate pass
    t0 = time.perf_counter()
    for _ in range(iters):
        roll.tick()
    tick_s = (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        ledger.sample()
    return tick_s, (time.perf_counter() - t0) / iters


def _ok_latency(rt, window=None):
    """(p50, p99, mean) seconds of ok-status requests, from the registry."""
    from repro.obs.metrics import window_summary

    h = rt.metrics.histogram("serving/latency_s", status="ok")
    s = h.summary() if window is None else window_summary(h, window)
    if s.get("n", 0) == 0:
        return 0.0, 0.0, 0.0
    return s["p50"], s["p99"], s["mean"]


def main(json_path: str = "BENCH_serving.json"):
    import numpy as np

    from benchmarks.common import all_records, emit
    from repro.core.engine import PAPER_QUERIES, KnowledgeBase
    from repro.obs.export import export_traces
    from repro.obs.trace import Tracer
    from repro.rdf.generator import generate_lubm
    from repro.serving.runtime import ServingRuntime

    records_before = len(all_records())
    n_clients = int(os.environ.get("REPRO_BENCH_SERVE_CLIENTS", "4"))
    per_client = int(os.environ.get("REPRO_BENCH_SERVE_PER_CLIENT", "40"))
    queries = list(PAPER_QUERIES.values())

    raw = generate_lubm(1, seed=0)
    K = KnowledgeBase.build(raw)
    s, p, o = np.asarray(raw.s), np.asarray(raw.p), np.asarray(raw.o)

    # -- read-only baseline: pins are all fast-path, plans prewarmed --------
    warm = max(2, per_client // 8)
    rt = ServingRuntime(K, modes=("litemat",), n_workers=n_clients,
                        max_queue=256)
    with rt:
        rt.registry.prewarm(queries)
        _closed_loop(rt, queries, n_clients, warm)
        win = rt.metrics.histogram("serving/latency_s", status="ok").state()
        outs, wall = _closed_loop(rt, queries, n_clients, per_client)
        p50, p99, untraced_mean = _ok_latency(rt, window=win)
    read_rps = len(outs) / max(wall, 1e-9)
    emit("serving/read_only", p50, p99_ms=round(p99 * 1e3, 2),
         requests_per_s=int(read_rps),
         n_ok=len(outs), n_triples=raw.n_triples)

    # -- micro-batched burst reads: same-signature coalescing ---------------
    # 8x the closed-loop client count over the same 2-worker budget: the
    # queue holds deep same-signature bursts, every drain coalesces ~30
    # peers, and the engine answers each duplicate cluster with ONE
    # executable dispatch (identical-signature members share it, identical
    # requests dedupe outright)
    burst = int(os.environ.get("REPRO_BENCH_SERVE_BURST", "32"))
    rt_b = ServingRuntime(K, modes=("litemat",), n_workers=2,
                          max_queue=512, batch_window_s=0.003,
                          max_batch=burst)
    with rt_b:
        rt_b.registry.prewarm(queries)
        _closed_loop(rt_b, queries, burst, warm)  # compile batched plans
        win = rt_b.metrics.histogram("serving/latency_s",
                                     status="ok").state()
        outs_b, wall_b = _closed_loop(rt_b, queries, burst, per_client)
        bp50, bp99, _ = _ok_latency(rt_b, window=win)
        occ = rt_b.metrics.histogram("serving/batch_size",
                                     kind="query").summary()
        n_batched = rt_b.stats["batched"]
    batched_rps = len(outs_b) / max(wall_b, 1e-9)
    occupancy = float(occ.get("mean", 0.0))
    emit("serving/batched_read", bp50, p99_ms=round(bp99 * 1e3, 2),
         requests_per_s=int(batched_rps),
         batch_occupancy=round(occupancy, 2),
         n_batched=n_batched, n_ok=sum(o.ok for o in outs_b))
    speedup = batched_rps / max(read_rps, 1e-9)
    emit("serving/batched_speedup", 0.0,
         speedup=round(speedup, 2), occupancy=round(occupancy, 2),
         baseline_rps=int(read_rps), batched_rps=int(batched_rps),
         gate_min_speedup=BATCHED_SPEEDUP_GATE,
         passed=bool(speedup >= BATCHED_SPEEDUP_GATE
                     and occupancy >= 4.0))

    # -- traced twin: the exported trace corpus + informational A/B --------
    tracer = Tracer()
    rt_t = ServingRuntime(K, modes=("litemat",), n_workers=n_clients,
                          max_queue=256, tracer=tracer)
    with rt_t:
        rt_t.registry.prewarm(queries)
        _closed_loop(rt_t, queries, n_clients, warm)
        win = rt_t.metrics.histogram("serving/latency_s",
                                     status="ok").state()
        _closed_loop(rt_t, queries, n_clients, per_client)
        _, _, traced_mean = _ok_latency(rt_t, window=win)
        traced_metrics = rt_t.metrics
    ab_pct = ((traced_mean - untraced_mean)
              / max(untraced_mean, 1e-12) * 100.0)

    # -- calibrated overhead gate: (tracer + control plane) / mean latency --
    from repro.obs.ledger import LEDGER

    traces = tracer.finished_traces()
    span_counts = sorted(len(t.spans) for t in traces) or [7]
    n_spans = span_counts[len(span_counts) // 2]
    cost_s = min(_tracer_cost_s(n_spans) for _ in range(3))
    # control-plane share: the rollup tick + ledger sample run once per
    # interval, not per request — amortize one (tick + sample) across the
    # requests a default 0.25 s interval admits at the measured rate
    K.track_ledger()
    tick_s, ledger_s = min(
        (_rollup_cost_s(traced_metrics, LEDGER) for _ in range(3)),
        key=sum)
    control_s = (tick_s + ledger_s) / (0.25 * max(read_rps, 1e-9))
    total_s = cost_s + control_s
    overhead_pct = total_s / max(untraced_mean, 1e-12) * 100.0
    emit("serving/obs_overhead", total_s,
         untraced_us=round(untraced_mean * 1e6, 1),
         overhead_pct=round(overhead_pct, 2),
         trace_pct=round(cost_s / max(untraced_mean, 1e-12) * 100.0, 2),
         control_plane_pct=round(
             control_s / max(untraced_mean, 1e-12) * 100.0, 2),
         rollup_tick_us=round(tick_s * 1e6, 1),
         ledger_sample_us=round(ledger_s * 1e6, 1),
         ab_overhead_pct=round(ab_pct, 2),
         spans_per_trace=n_spans,
         n_traces=len(traces),
         gate_max_pct=OBS_OVERHEAD_GATE_PCT,
         passed=bool(overhead_pct <= OBS_OVERHEAD_GATE_PCT))

    trace_path = os.environ.get("REPRO_TRACE_EXPORT")
    if trace_path:
        n = export_traces(tracer, trace_path)
        print(f"# wrote {trace_path} ({n} traces)")

    # -- mixed workload: the same read stream racing a background writer ----
    rt = ServingRuntime(K, modes=("litemat",), n_workers=n_clients,
                        max_queue=256, pin_lock_timeout_s=0.05)
    with rt:
        rt.registry.prewarm(queries)
        stop = threading.Event()

        def writer():
            rng = np.random.default_rng(1)
            while not stop.is_set():
                i = int(rng.integers(0, max(s.shape[0] - 64, 1)))
                rt.insert((s[i:i + 64], p[i:i + 64], o[i:i + 64]),
                          auto_compact=False)
                if stop.wait(0.02):
                    return

        w = threading.Thread(target=writer, daemon=True)
        t0 = time.perf_counter()
        w.start()
        # warmup epoch: grow the delta past its first pow2 bucket
        # transitions so their plan compiles land outside the measurement
        _closed_loop(rt, queries, n_clients, 8)
        warm_stats = dict(rt.stats)
        window = rt.metrics.histogram("serving/latency_s",
                                      status="ok").state()
        outs, wall = _closed_loop(rt, queries, n_clients, per_client)
        stop.set()
        w.join()
        write_wall = time.perf_counter() - t0
        p50, p99, _ = _ok_latency(rt, window=window)
        stats = dict(rt.stats)
        mixed_metrics = rt.metrics
    n_ok = stats["ok"] - warm_stats["ok"]
    n_measured_stale = (stats["stale_served"] - warm_stats["stale_served"])
    emit("serving/mixed_workload", p50, p99_ms=round(p99 * 1e3, 2),
         requests_per_s=int(len(outs) / max(wall, 1e-9)),
         update_rows_per_s=int(64 * stats["updates"]
                               / max(write_wall, 1e-9)),
         n_ok=n_ok, n_updates=stats["updates"],
         n_stale_served=n_measured_stale, n_retries=stats["retries"])
    slo_ok = (stats["shed"] == 0 and stats["deadline"] == 0
              and n_ok == len(outs))
    emit("serving/mixed_slo", 0.0, shed=stats["shed"],
         deadline_missed=stats["deadline"], errors=stats["errors"],
         passed=bool(slo_ok))

    metrics_path = os.environ.get("REPRO_METRICS_EXPORT")
    if metrics_path:
        # one aggregated fleet-schema snapshot: the mixed run's serving
        # registry + the process-global engine registry (plan cache,
        # capacity retries, ledger hbm gauges) as two labelled members —
        # schema-validated by scripts/check_traces.py, rendered by
        # scripts/fleet_report.py
        from repro.obs.aggregate import aggregate
        from repro.obs.metrics import REGISTRY

        LEDGER.sample()
        fleet = aggregate([
            mixed_metrics.mergeable_snapshot(process="serving"),
            REGISTRY.mergeable_snapshot(process="engine"),
        ])
        with open(metrics_path, "w") as f:
            json.dump(fleet, f, indent=1, sort_keys=True)
        print(f"# wrote {metrics_path} (fleet schema, "
              f"{len(fleet['histograms'])} histograms)")

    if json_path:
        rows = all_records()[records_before:]
        artifact = {
            "n_base_triples": raw.n_triples,
            "n_requests": n_clients * per_client,
            "rows": rows,
        }
        with open(json_path, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"# wrote {json_path} ({len(rows)} rows)")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    main()
