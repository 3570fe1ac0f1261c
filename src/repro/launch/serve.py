"""Serving launcher: batched LiteMat query serving (the paper's workload).

``python -m repro.launch.serve --universities 2 --requests 1024`` builds a
LUBM-style KB, encodes + lite-materializes it, then serves batches of
parameterized class/member queries through the vmapped plans, reporting
throughput and p50/p99 latencies.

``--concurrent`` switches to the snapshot-isolated request runtime
(serving/runtime.py): N submitter threads drive Q1–Q4 through the bounded
admission queue while a writer thread streams inserts/deletes, and the
report adds shed/deadline/stale counts on top of the latency percentiles.
"""
from __future__ import annotations

import argparse
import threading
import time

import numpy as np

from repro.core.engine import PAPER_QUERIES, KnowledgeBase
from repro.rdf.generator import generate_lubm
from repro.serving.engine import QueryServer
from repro.serving.runtime import ServingRuntime
from repro.utils.compile_cache import enable_compile_cache

CLASSES = ["Professor", "Student", "Faculty", "Person", "Course",
           "Publication", "Organization", "Department", "Chair",
           "GraduateStudent"]
PROPS = ["memberOf", "worksFor", "degreeFrom", "takesCourse", "advisor"]


def run_concurrent(K, raw, args) -> None:
    """Mixed workload through the snapshot-isolated runtime."""
    queries = list(PAPER_QUERIES.values())
    rt = ServingRuntime(
        K, modes=("litemat",), n_workers=args.workers,
        max_queue=args.max_queue, default_deadline_s=args.deadline_s)
    with rt:
        rt.registry.prewarm(queries)
        s, p, o = np.asarray(raw.s), np.asarray(raw.p), np.asarray(raw.o)
        stop = threading.Event()

        def writer():
            rng = np.random.default_rng(args.seed + 1)
            while not stop.is_set():
                i = int(rng.integers(0, max(s.shape[0] - 64, 1)))
                rt.insert((s[i:i + 64], p[i:i + 64], o[i:i + 64]),
                          auto_compact=False)
                if stop.wait(0.01):
                    return

        w = threading.Thread(target=writer, daemon=True)
        w.start()
        futs = [rt.submit(queries[i % len(queries)])
                for i in range(args.requests)]
        outs = [f.result() for f in futs]
        stop.set()
        w.join()
    n_ok = sum(o.ok for o in outs)
    lat = rt.latency_stats()
    print(f"concurrent: {n_ok}/{len(outs)} ok "
          f"p50={lat.get('p50_ms', 0):.2f}ms p99={lat.get('p99_ms', 0):.2f}ms "
          f"stats={rt.stats}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--universities", type=int, default=1)
    ap.add_argument("--requests", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--concurrent", action="store_true",
                    help="drive the snapshot-isolated request runtime "
                         "(readers + background update stream)")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--deadline-s", type=float, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    print(f"generating LUBM-like KB ({args.universities} universities)...")
    raw = generate_lubm(args.universities, seed=args.seed)
    t0 = time.time()
    K = KnowledgeBase.build(raw)
    print(f"encoded+materialized {raw.n_triples:,} triples in {time.time()-t0:.1f}s "
          f"(sizes: {K.sizes()})")

    if args.concurrent:
        return run_concurrent(K, raw, args)

    srv = QueryServer(K)
    rng = np.random.default_rng(args.seed)
    lat = []
    served = 0
    t0 = time.time()
    while served < args.requests:
        b = min(args.batch, args.requests - served)
        names = [CLASSES[i] for i in rng.integers(0, len(CLASSES), b)]
        t1 = time.time()
        if served % (2 * args.batch) < args.batch:
            counts, _ = srv.class_members(names)
        else:
            props = [PROPS[i] for i in rng.integers(0, len(PROPS), b)]
            counts, _ = srv.class_prop_join(names, props)
        lat.append((time.time() - t1) / b)
        served += b
    wall = time.time() - t0
    lat_ms = np.array(lat) * 1000
    print(f"served {served} queries in {wall:.2f}s -> {served/wall:,.0f} q/s; "
          f"per-query p50={np.percentile(lat_ms,50):.2f}ms "
          f"p99={np.percentile(lat_ms,99):.2f}ms (amortized)")


if __name__ == "__main__":
    main()
