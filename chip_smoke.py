#!/usr/bin/env python3
"""Smoke run of the LiteMat store on TPU: one chip, or four with --chips 4.

    python chip_smoke.py                     # one chip, LUBM-20
    python chip_smoke.py --universities 100  # a larger store
    python chip_smoke.py --chips 4           # the sharded store on four chips

One chip drives the served path through the entry points a user calls
(``KnowledgeBase.build``, ``ServingRuntime``, ``QueryServer``):

  1. oracle: LUBM-1, Q1-Q4 in litemat/full/rewrite served through the
     runtime, each answer set equal to the naive reference KB
     (tests/oracle.py) in fingerprint space;
  2. scale: LUBM-``--universities`` built; one more university inserted
     and a tenth of it deleted; the plans prewarmed; Q1-Q4 x three modes
     submitted at once so the runtime coalesces them; modes must agree,
     indexed plans must equal scan plans, one ``class_members`` batch
     must agree with Q1; then ``compact()`` (the device merge on TPU)
     folds the delta and every answer must come back unchanged;
  3. counters: every runtime/shard fallback counter reads 0 and the
     compaction kernels were traced (``kernels/passes`` > 0).

The oracle phase runs on a second thread while the scale store builds:
the chip's compiler takes seconds per executable that sorts, and
compiles proceed outside the GIL.  For the same reason the plans are
prewarmed (all modes compiling at once) for the store they will serve:
after the insert and delete, and again after the compaction.

``--chips 4`` instead ingests the same store into a four-shard
``ShardedKB`` (one shard per chip) and checks it against the one-chip
``KnowledgeBase``: Q1-Q4 x three modes through ``shard_map`` and the
all-to-all repartition join, with every shard's buffers on its own chip.

Everything runs in this one process, which holds the chips.  Any failed
check, or a JAX backend that is not a TPU, exits non-zero and prints no
result line.  Informative lines carry the seconds since start and name
the device they came from; the last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from importlib import metadata
from pathlib import Path

ROOT = Path(__file__).resolve().parent
MODES = ("litemat", "full", "rewrite")
QUERIES = ("Q1", "Q2", "Q3", "Q4")
COPIES = 4  # identical requests per (query, mode): coalesced batches
T0 = time.perf_counter()
COMPILES = None  # Compiles, once JAX is up


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def tag(dev) -> str:
    """Label for an informative line: the device it was measured on."""
    return f"[{dev.device_kind} id={dev.id}]"


class Compiles:
    """XLA compiles and persistent-cache hits, counted from JAX's
    monitoring events (compile seconds summed over threads)."""

    def __init__(self):
        import jax

        self.lock = threading.Lock()
        self.n = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._compiled)
        jax.monitoring.register_event_listener(self._event)

    def _compiled(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self.lock:
                self.n += 1
                self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self.lock:
                self.hits += 1

    def line(self) -> str:
        with self.lock:
            return (f"{self.n} executables compiled ({self.seconds:.3f}s, "
                    f"summed over threads), {self.hits} read from the "
                    f"compile cache")


def _fp_set(K, answers) -> set:
    """Id tuples -> fingerprint tuples (the identity NaiveKB and a
    re-encoded store agree on)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.utils import pair64

    if not answers:
        return set()
    rows = np.asarray(sorted(answers), dtype=np.int32)
    hi, lo, hit = K.kb.table.extract_fp(jnp.asarray(rows.reshape(-1)))
    fps = pair64.combine_np(np.asarray(hi), np.asarray(lo))
    fps = np.where(np.asarray(hit), fps, rows.reshape(-1))
    return {tuple(r) for r in fps.reshape(rows.shape).tolist()}


def _queries():
    from repro.core.engine import PAPER_QUERIES

    return [PAPER_QUERIES[q] for q in QUERIES]


def _selects():
    from oracle import query_vars

    from repro.core.engine import PAPER_QUERIES

    return {q: query_vars(PAPER_QUERIES[q]) for q in QUERIES}


def _serve_all(rt, sel, copies: int = 1) -> dict:
    """Submit every (query, mode) ``copies`` times at once; -> answers."""
    from repro.core.engine import PAPER_QUERIES

    futs = [(q, m, rt.submit(PAPER_QUERIES[q], select=sel[q], mode=m))
            for m in MODES for q in QUERIES for _ in range(copies)]
    out = {}
    for q, m, f in futs:
        o = f.result()
        check(o.ok, f"{q}/{m} outcome {o.status}: {o.error}")
        prev = out.setdefault((q, m), o.answers)
        check(prev == o.answers, f"{q}/{m}: identical requests disagree")
    return out


def _runtime(K):
    from repro.serving.runtime import ServingRuntime

    return ServingRuntime(K, modes=MODES, n_workers=2, max_queue=256,
                          batch_window_s=0.05, max_batch=16)


def _prewarm(rt, sel) -> float:
    """Compile every (mode, query) plan for the published store at once."""
    t0 = time.perf_counter()
    rt.registry.prewarm(_queries(), modes=MODES,
                        selects=[sel[q] for q in QUERIES])
    return time.perf_counter() - t0


def oracle_phase(seed: int, runtimes: list) -> None:
    from oracle import NaiveKB

    from repro.core.engine import PAPER_QUERIES, KnowledgeBase
    from repro.rdf.generator import generate_lubm

    t0 = time.perf_counter()
    raw = generate_lubm(1, seed=seed)
    K = KnowledgeBase.build(raw)
    naive = NaiveKB(raw.onto)
    naive.insert(raw)
    sel = _selects()
    rt = _runtime(K)
    runtimes.append(rt)
    with rt:
        _prewarm(rt, sel)
        got = _serve_all(rt, sel)
    for q in QUERIES:
        want = naive.answers(PAPER_QUERIES[q], sel[q])
        check(len(want) > 0, f"oracle {q}: empty reference answer")
        for m in MODES:
            check(_fp_set(K, got[(q, m)]) == want,
                  f"oracle {q}/{m}: differs from the naive reference")
    log(f"oracle: LUBM-1 ({raw.s.shape[0]} raw triples) Q1-Q4 x "
        f"{'/'.join(MODES)} equal the naive reference "
        f"({time.perf_counter() - t0:.3f}s); so far {COMPILES.line()}")


def scale_phase(universities: int, seed: int, runtimes: list, dev) -> None:
    import jax

    from repro.core.engine import PAPER_QUERIES, KnowledgeBase
    from repro.obs.ledger import LEDGER
    from repro.obs.metrics import REGISTRY
    from repro.rdf.generator import generate_lubm
    from repro.utils.parallel import run_concurrently

    t0 = time.perf_counter()
    raw = generate_lubm(universities, seed=seed)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    K = KnowledgeBase.build(raw)
    jax.block_until_ready((K.kb.spo, K.lite_spo, K.full_spo))
    build_s = time.perf_counter() - t0
    log(f"{tag(dev)} LUBM-{universities}: {raw.s.shape[0]} raw triples "
        f"generated in {gen_s:.3f}s, built in {build_s:.3f}s, "
        f"sizes {K.sizes()}; so far {COMPILES.line()}")

    sel = _selects()
    q1 = PAPER_QUERIES["Q1"]
    pool = generate_lubm(1, seed=seed + 1, univ_offset=universities)
    k = pool.s.shape[0] // 10
    rt = _runtime(K)
    runtimes.append(rt)
    with rt:
        o = rt.submit(q1, select=sel["Q1"], mode="litemat").result()
        check(o.ok, f"Q1 before the insert: {o.status}: {o.error}")
        n_q1 = len(o.answers)
        t0 = time.perf_counter()
        rt.insert(pool)
        rt.delete((pool.s[:k], pool.p[:k], pool.o[:k]))
        log(f"{tag(dev)} insert {pool.s.shape[0]} + delete {k} triples: "
            f"{time.perf_counter() - t0:.3f}s")
        t0 = time.perf_counter()
        run_concurrently([  # the served plans and the scan plans at once
            partial(_prewarm, rt, sel),
            partial(K.prewarm, _queries(), modes=MODES, use_index=False,
                    selects=[sel[q] for q in QUERIES])])
        log(f"{tag(dev)} prewarm Q1-Q4 x {len(MODES)} modes, indexed and "
            f"scan plans: {time.perf_counter() - t0:.3f}s")
        served = _serve_all(rt, sel, copies=COPIES)
        batched = rt.metrics.counter_value("serving/batched")
        check(batched > 0, "no request was served in a coalesced batch")
        log(f"{tag(dev)} served {len(served) * COPIES} requests, "
            f"{batched} in coalesced batches")
        check(len(served[("Q1", "litemat")]) > n_q1,
              "the inserted university added no professor")

        for q in QUERIES:
            ref = served[(q, "litemat")]
            check(len(ref) > 0, f"{q}: empty answer at scale")
            for m in MODES:
                check(served[(q, m)] == ref, f"{q}: {m} != litemat")
                scan = K.answers(PAPER_QUERIES[q], select=sel[q], mode=m,
                                 use_index=False)
                check(scan == served[(q, m)], f"{q}/{m}: indexed != scan")
        log(f"{tag(dev)} modes agree and indexed == scan for Q1-Q4")

        names = ["Professor", "Student", "Chair", "Department"]
        out = rt.class_members(names)
        check(out.ok, f"class_members outcome {out.status}: {out.error}")
        counts, members = out.answers
        profs = sorted(a[0] for a in served[("Q1", "litemat")])
        check(int(counts[0]) == len(profs), "class_members(Professor) != |Q1|")
        top = [int(v) for v in members[0] if v >= 0]
        check(top == profs[:len(top)] and top,
              "class_members top-k != Q1 head")
        log(f"{tag(dev)} class_members batch of {len(names)}: counts "
            f"{counts.tolist()}")

        with rt.registry.pin() as pin:  # the runtime's compiled plans
            for q in QUERIES:
                for m in MODES:
                    times = []
                    for _ in range(3):
                        t0 = time.perf_counter()
                        rows, _ = pin.query(PAPER_QUERIES[q], select=sel[q],
                                            mode=m)
                        jax.block_until_ready(rows)
                        times.append(time.perf_counter() - t0)
                    log(f"{tag(dev)} warm {q}/{m}: "
                        f"{statistics.median(times):.6f}s "
                        f"({len(served[(q, m)])} rows)")

        t0 = time.perf_counter()
        stats = rt.compact()
        compact_s = time.perf_counter() - t0
        check(stats.get("compacted"), f"compact() did nothing: {stats}")
        log(f"{tag(dev)} compact: {compact_s:.3f}s; prewarm again: "
            f"{_prewarm(rt, sel):.3f}s")
        after = _serve_all(rt, sel)
        for key, ans in after.items():
            check(served[key] == ans, f"{key}: answers changed across compact")
        log(f"{tag(dev)} answers unchanged across compact")

    compile_s = sum(v.get("sum", 0.0) for name, v in
                    REGISTRY.snapshot()["histograms"].items()
                    if name.startswith("query/compile_seconds"))
    K.track_ledger()
    bpt = LEDGER.sample()["bytes_per_triple"]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"{tag(dev)} cold compile (query/compile_seconds total): "
        f"{compile_s:.3f}s")
    log(f"{tag(dev)} store/bytes_per_triple: {bpt}")
    log(f"{tag(dev)} peak_bytes_in_use: {peak}")


def check_counters(runtimes: list) -> None:
    from repro.obs.metrics import REGISTRY

    fallback = sum(sum(rt.metrics.values("serving/batch_fallback").values())
                   for rt in runtimes)
    publish = sum(rt.metrics.counter_value("serving/publish_failures")
                  for rt in runtimes)
    sm_faults = REGISTRY.counter_value("shard/shard_map_faults")
    ex_faults = REGISTRY.counter_value("shard/exchange_faults")
    passes = sum(REGISTRY.values("kernels/passes").values())
    log(f"counters: serving/batch_fallback={fallback} "
        f"serving/publish_failures={publish} "
        f"shard/shard_map_faults={sm_faults} "
        f"shard/exchange_faults={ex_faults} kernels/passes={passes}")
    check(fallback == 0, "a batched execution fell back to solo runs")
    check(publish == 0, "a snapshot publish failed")
    check(sm_faults == 0, "the shard_map executable fell back")
    check(ex_faults == 0, "the repartition join fell back")
    check(passes > 0, "no compaction kernel was traced")


def four_chip_phase(universities: int, seed: int, dev) -> None:
    import jax
    import numpy as np

    from repro.core.engine import PAPER_QUERIES, KnowledgeBase
    from repro.core.shard import ShardedKB
    from repro.obs.metrics import REGISTRY
    from repro.rdf.generator import generate_lubm
    from repro.utils.parallel import run_concurrently

    check(len(jax.devices()) >= 4, f"--chips 4 needs four devices, found "
          f"{len(jax.devices())}")
    raw = generate_lubm(universities, seed=seed)

    def build():
        t0 = time.perf_counter()
        K = KnowledgeBase.build(raw)
        jax.block_until_ready(K.full_spo)
        log(f"{tag(dev)} one-chip LUBM-{universities} ({raw.s.shape[0]} raw "
            f"triples) built in {time.perf_counter() - t0:.3f}s")
        return K

    def ingest():
        cuts = np.linspace(0, raw.s.shape[0], 9).astype(int)
        parts = ((raw.s[a:b], raw.p[a:b], raw.o[a:b])
                 for a, b in zip(cuts[:-1], cuts[1:]))
        t0 = time.perf_counter()
        S = ShardedKB.ingest(parts, onto=raw.onto, n_shards=4)
        log(f"{tag(dev)} four-shard ingest in "
            f"{time.perf_counter() - t0:.3f}s")
        return S

    K, S = run_concurrently([build, ingest])  # their compiles overlap

    sel = _selects()
    for m in MODES:
        eng = S.engine(m)
        check(eng._shard_map_on() and eng._repartition_on(),
              f"{m}: shard_map / repartition join not on")

    def sharded(m):  # one mode's queries, first runs timed
        out = {}
        for q in QUERIES:
            t0 = time.perf_counter()
            got, _ = S.query(PAPER_QUERIES[q], select=sel[q], mode=m)
            out[q] = (got, time.perf_counter() - t0)
        return out

    # the one-chip plans and each mode's sharded plans compile at once
    t0 = time.perf_counter()
    _, *runs = run_concurrently(
        [partial(K.prewarm, _queries(), modes=MODES,
                 selects=[sel[q] for q in QUERIES])]
        + [partial(sharded, m) for m in MODES])
    log(f"{tag(dev)} one-chip prewarm and sharded first runs: "
        f"{time.perf_counter() - t0:.3f}s")
    for m, out in zip(MODES, runs):
        for q in QUERIES:
            got, dt = out[q]
            want, _ = K.query(PAPER_QUERIES[q], select=sel[q], mode=m)
            a = _fp_set(S, {tuple(r) for r in np.asarray(got).tolist()})
            b = _fp_set(K, {tuple(r) for r in np.asarray(want).tolist()})
            check(a == b and b, f"{q}/{m}: sharded != one-chip store")
            log(f"{tag(dev)} x4 {q}/{m}: {len(a)} rows equal, first run "
                f"{dt:.3f}s")
    sm_runs = sum(S.engine(m).cache_stats["shard_map_runs"] for m in MODES)
    rp = REGISTRY.counter_value("shard/combine_runs", path="repartition")
    log(f"shard_map_runs={sm_runs} shard/combine_runs{{path=repartition}}={rp}")
    check(sm_runs > 0, "no plan ran through shard_map")
    check(rp > 0, "no join ran through the repartition exchange")

    devs = S.shard_devices()
    check(len(set(devs)) == 4, f"shards share devices: {devs}")
    for i, shard in enumerate(S.shards):
        for arr in (shard.kb.spo, shard.lite_spo, shard.full_spo):
            check(arr.devices() == {devs[i]},
                  f"shard {i} store on {arr.devices()}, not {devs[i]}")
    n_stacks = 0
    for m in MODES:
        for stack in S.engine(m)._stacks.values():
            for st in stack._states.values():
                for i, s in enumerate(st["base"].addressable_shards):
                    check(s.data.shape[0] == 1 and s.device == devs[i],
                          f"stack slab {i} on {s.device}, not {devs[i]}")
                n_stacks += 1
    check(n_stacks > 0, "no ShardStack was built")
    log(f"shards on {[str(d) for d in devs]}; {n_stacks} stacked slabs "
        f"split one per chip")
    check_counters([])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    # LUBM-100 builds in about ten minutes on one v5e, and the whole run
    # must fit in twenty minutes from a cold compile cache
    ap.add_argument("--universities", type=int, default=20,
                    help="LUBM scale of the store (default 20)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded four-chip phase")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX backend is {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    global COMPILES
    COMPILES = Compiles()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    log(f"device: {dev.device_kind} x{len(jax.devices())} "
        f"(jax {jax.__version__}, libtpu {libtpu}); compile cache {cache}")
    try:
        if args.chips == 4:
            four_chip_phase(args.universities, args.seed, dev)
        else:
            runtimes = []
            with ThreadPoolExecutor(1) as pool:
                oracle = pool.submit(oracle_phase, args.seed, runtimes)
                scale_phase(args.universities, args.seed, runtimes, dev)
                oracle.result()
            check_counters(runtimes)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    log(f"{tag(dev)} in all: {COMPILES.line()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
