"""Multi-process jax.distributed smoke: 2 processes x 4 forced devices.

CI launches this script twice (process 0 is the coordinator) with
``--xla_force_host_platform_device_count=4`` per process, so the global
runtime sees 8 devices across 2 processes — the smallest shape that
exercises the multi-host runtime the repartition join targets.

Each process:
  1. initializes ``jax.distributed`` and checks the global/local device
     topology,
  2. runs a cross-process collective (psum over the global mesh) to prove
     the exchange fabric the all-to-all repartition rides on is live,
  3. builds a ShardedKB over its LOCAL devices and runs the repartition
     join + sharded-encode ingest parity against the single-device engine
     (per-process store placement is still local-device scoped; the global
     mesh migration is tracked in ROADMAP item 2).

Usage (CI runs both, backgrounding process 1):
    python scripts/distributed_smoke.py --process-id 0 --num-processes 2
    python scripts/distributed_smoke.py --process-id 1 --num-processes 2
"""
from __future__ import annotations

import argparse
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--coordinator", default="127.0.0.1:9955")
    ap.add_argument("--num-processes", type=int, default=2)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--local-devices", type=int, default=4)
    ap.add_argument("--metrics-dir", default="",
                    help="export per-process mergeable metrics snapshots "
                         "here; process 0 aggregates them into fleet.json")
    args = ap.parse_args()

    import jax

    jax.distributed.initialize(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    )
    import jax.numpy as jnp
    import numpy as np

    nglobal = args.num_processes * args.local_devices
    assert jax.device_count() == nglobal, (jax.device_count(), nglobal)
    assert jax.local_device_count() == args.local_devices

    # 1. cross-process collective over the GLOBAL mesh: the exchange fabric
    out = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
        jnp.ones((jax.local_device_count(),), jnp.int32))
    assert int(np.asarray(out)[0]) == nglobal, np.asarray(out)
    print(f"[proc {args.process_id}] collective OK: psum={int(out[0])} "
          f"over {nglobal} devices / {args.num_processes} processes",
          flush=True)

    # 2. repartition-join parity over this process's local devices
    from repro.core.engine import KnowledgeBase, PAPER_QUERIES
    from repro.core.shard import ShardedKB
    from repro.obs.metrics import REGISTRY
    from repro.rdf.generator import generate_lubm

    raw = generate_lubm(1, seed=7)
    K = KnowledgeBase.build(raw)
    S = ShardedKB.build(raw, n_shards=args.local_devices)
    S.track_ledger()  # per-shard hbm_bytes gauges ride the metrics export
    eng = S.engine("litemat")
    assert eng._shard_map_on() and eng._repartition_on()
    c = REGISTRY.counter("device/transfer_bytes", src="combine_upload")
    before = c.value
    want, _ = K.query(PAPER_QUERIES["Q4"], mode="litemat")
    got, _ = eng.run(PAPER_QUERIES["Q4"])
    assert np.array_equal(np.asarray(got), want)
    assert eng.cache_stats["repartition_runs"] >= 1, eng.cache_stats
    assert c.value == before, "device combine leaked a host re-upload"
    print(f"[proc {args.process_id}] repartition join OK: "
          f"{want.shape[0]} rows, zero host uploads", flush=True)

    # 3. sharded-encode ingest on local devices stays fp-space identical
    from repro.core.tbox import build_tbox
    from repro.utils import pair64

    n = raw.s.shape[0]
    half = n // 2
    parts = [(raw.s[:half], raw.p[:half], raw.o[:half]),
             (raw.s[half:], raw.p[half:], raw.o[half:])]
    SI = ShardedKB.ingest(iter(parts), onto=raw.onto,
                          n_shards=args.local_devices)
    assert SI.use_sharded_encode and SI._sharded_encode_on()

    def answers_fp(kb, pats):
        rows, _ = kb.query(pats, mode="litemat")
        if rows.size == 0:
            return set()
        ids = jnp.asarray(np.asarray(rows).reshape(-1).astype(np.int32))
        hi, lo, hit = kb.kb.table.extract_fp(ids)
        fps = pair64.combine_np(np.asarray(hi), np.asarray(lo))
        fps = np.where(np.asarray(hit), fps, np.asarray(rows).reshape(-1))
        return {tuple(r) for r in fps.reshape(rows.shape).tolist()}

    ctrl = ShardedKB.empty(build_tbox(raw.onto), n_shards=args.local_devices)
    for p in parts:
        ctrl.insert(p, auto_compact=False)
    a = answers_fp(SI, PAPER_QUERIES["Q1"])
    assert a == answers_fp(ctrl, PAPER_QUERIES["Q1"]) and len(a) > 0
    print(f"[proc {args.process_id}] sharded encode OK: {len(a)} answers",
          flush=True)

    # 4. cross-process telemetry: every process exports a mergeable
    # snapshot; process 0 waits for its peers' files and aggregates them
    # into ONE schema-validated fleet snapshot (the artifact CI uploads).
    if args.metrics_dir:
        _export_and_aggregate(args)

    print(f"[proc {args.process_id}] DISTRIBUTED SMOKE PASSED", flush=True)
    return 0


def _export_and_aggregate(args) -> None:
    import json
    import os
    import time

    from repro.obs.aggregate import aggregate, check_compatible
    from repro.obs.export import (export_mergeable_metrics,
                                  validate_metrics_snapshot)
    from repro.obs.ledger import LEDGER
    from repro.obs.metrics import REGISTRY

    os.makedirs(args.metrics_dir, exist_ok=True)
    LEDGER.sample()  # land hbm_bytes/bytes_per_triple gauges pre-export
    mine = os.path.join(args.metrics_dir,
                        f"metrics-proc{args.process_id}.json")
    snap = export_mergeable_metrics(REGISTRY, mine,
                                    process=str(args.process_id))
    print(f"[proc {args.process_id}] exported {len(snap['counters'])} "
          f"counters / {len(snap['histograms'])} histograms -> {mine}",
          flush=True)
    if args.process_id != 0:
        return

    paths = [os.path.join(args.metrics_dir, f"metrics-proc{i}.json")
             for i in range(args.num_processes)]
    deadline = time.monotonic() + 60.0
    snaps = {}
    while len(snaps) < len(paths):
        for p in paths:
            if p in snaps or not os.path.exists(p):
                continue
            try:
                with open(p) as f:
                    snaps[p] = json.load(f)
            except json.JSONDecodeError:
                continue  # peer mid-write: retry next poll
        if len(snaps) < len(paths):
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"peer snapshots missing: "
                    f"{[p for p in paths if p not in snaps]}")
            time.sleep(0.2)
    ordered = [snaps[p] for p in paths]
    for p, s in zip(paths, ordered):
        errors = validate_metrics_snapshot(s)
        assert not errors, (p, errors)
    check_compatible(ordered)
    fleet = aggregate(ordered)
    errors = validate_metrics_snapshot(fleet)
    assert not errors, errors
    # counters must SUM across processes: every process ran the same
    # repartition check, so the fleet's run counter is n_processes times
    # any single process's
    key = "shard/combine_runs"
    mine_runs = sum(e["value"] for e in ordered[0]["counters"]
                    if e["name"] == key)
    fleet_runs = sum(e["value"] for e in fleet["counters"]
                     if e["name"] == key)
    per_proc = [sum(e["value"] for e in s["counters"] if e["name"] == key)
                for s in ordered]
    assert fleet_runs == sum(per_proc) and mine_runs > 0, (
        fleet_runs, per_proc)
    # histogram counts must merge bucket-wise (sum of member counts)
    fh = {(e["name"], tuple(sorted(e["labels"].items()))): e
          for e in fleet["histograms"]}
    for s in ordered:
        for e in s["histograms"]:
            k = (e["name"], tuple(sorted(e["labels"].items())))
            assert k in fh, k
    out = os.path.join(args.metrics_dir, "fleet.json")
    with open(out, "w") as f:
        json.dump(fleet, f, indent=1, sort_keys=True)
    print(f"[proc 0] fleet aggregation OK: {len(ordered)} processes -> "
          f"{out} ({fleet_runs} combine runs fleet-wide)", flush=True)


if __name__ == "__main__":
    sys.exit(main())
