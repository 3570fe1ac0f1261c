# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark orchestrator.

    PYTHONPATH=src python -m benchmarks.run [--only table3,table6]

Sections (paper table -> module):
    table2 -> bench_tbox          TBox encoding time vs ontology size
    table3 -> bench_abox          SAE vs OBE ABox encoding throughput
    table4/5 -> bench_materialize lite vs full materialization
    table6 -> bench_queries       Q1-Q4 across lite/full/rewrite (+serving)
    updates -> bench_updates      incremental insert/delete/compact vs
                                  rebuild (writes BENCH_updates.json)
    serving -> bench_serving      snapshot-isolated runtime latency under
                                  concurrent reads + background updates
                                  (writes BENCH_serving.json)
    kernels -> bench_kernels      Pallas kernels vs refs

Scale via env: REPRO_BENCH_UNIV (default 4 universities ~ 0.5M triples).
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. table3,table6")
    ap.add_argument("--json", default="BENCH_queries.json",
                    help="machine-readable artifact path ('' disables)")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_abox, bench_kernels, bench_materialize, bench_queries,
        bench_serving, bench_tbox, bench_updates,
    )

    sections = {
        "table2": bench_tbox.main,
        "table3": bench_abox.main,
        "table45": bench_materialize.main,
        "table6": bench_queries.main,
        "updates": bench_updates.main,
        "serving": bench_serving.main,
        "kernels": bench_kernels.main,
    }
    chosen = (
        {k.strip() for k in args.only.split(",")} if args.only else set(sections)
    )
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in sections.items():
        if name not in chosen:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,{type(e).__name__}:{e}", file=sys.stderr)
            raise
    print(f"# total bench wall: {time.time() - t0:.1f}s")

    if args.json:
        from benchmarks.common import BENCH_UNIVERSITIES, all_records

        artifact = {
            "bench_universities": BENCH_UNIVERSITIES,
            "sections": sorted(chosen & set(sections)),
            "wall_seconds": round(time.time() - t0, 1),
            "rows": all_records(),
        }
        with open(args.json, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"# wrote {args.json} ({len(artifact['rows'])} rows)")


if __name__ == "__main__":
    main()
