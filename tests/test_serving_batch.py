"""Micro-batched serving + pagination: correctness, isolation, bugfixes.

The batching contract under test: a burst of same-signature requests
answered through the runtime's coalesced path must be BIT-IDENTICAL to
per-request ``serve()`` in every mode, each member must carry its own
Outcome (version, stale, trace_id), and a member that faults must not
poison its batchmates.  Pagination: the union of all pages equals the
unpaginated answer set at the pinned version, and a cursor whose version
was retired degrades to a stale fresh-pin page instead of erroring.
Plus the runtime bugfix sweep: the start() double-start race, the
shed-path trace leak, and the unbounded latency list.
"""
import threading

import numpy as np

from repro.core.engine import KnowledgeBase, PAPER_QUERIES
from repro.core.query import QueryEngine
from repro.obs.export import validate_trace
from repro.obs.trace import Tracer
from repro.serving.runtime import Cursor, ServingRuntime
from repro.testing import faults


def _burst(rt, queries, **kw):
    futs = [rt.submit(q, **kw) for q in queries]
    return [f.result() for f in futs]


def _fresh_engine(K, mode="litemat"):
    """A private engine — the KB's cached one is shared session state."""
    return QueryEngine(kb=K.kb, spo=K._base_store(mode), mode=mode,
                       dtb=K.dtb, view=K.view(mode))


# -- batched answers == solo answers ----------------------------------------


def test_batched_answers_match_solo_across_modes(lubm_kb):
    K, _ = lubm_kb
    qs = list(PAPER_QUERIES.values())
    rt = ServingRuntime(K, modes=("litemat", "full", "rewrite"),
                        n_workers=1, batch_window_s=0.05, max_batch=16)
    with rt:
        for mode in ("litemat", "full", "rewrite"):
            solo = [rt.serve(q, mode=mode) for q in qs]
            assert all(o.ok for o in solo)
            burst = _burst(rt, [qs[i % len(qs)] for i in range(16)],
                           mode=mode)
            assert all(o.ok for o in burst)
            for i, out in enumerate(burst):
                assert out.answers == solo[i % len(qs)].answers, mode
                assert out.version is not None
        assert rt.stats["batched"] > 0
        occ = rt.metrics.histogram("serving/batch_size",
                                   kind="query").summary()
        assert occ["n"] > 0 and occ["max"] >= 2


def test_batch_members_carry_own_outcomes(lubm_kb):
    """Every member of a coalesced batch gets its own version / trace_id,
    and the batched spans export as well-formed traces."""
    K, _ = lubm_kb
    qs = list(PAPER_QUERIES.values())
    tracer = Tracer()
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1,
                        batch_window_s=0.05, max_batch=8, tracer=tracer)
    with rt:
        outs = _burst(rt, [qs[i % len(qs)] for i in range(8)])
    assert all(o.ok for o in outs)
    ids = [o.trace_id for o in outs]
    assert len(set(ids)) == len(ids) and all(ids)
    versions = {o.version for o in outs}
    assert len(versions) == 1  # one read-only burst, one consistent version
    by_id = {t.trace_id: t for t in tracer.finished_traces()}
    saw_batched = False
    for o in outs:
        tr = by_id[o.trace_id]
        assert validate_trace(tr.to_dict()) == []
        for sp in tr.find("serving.attempt"):
            if sp.attrs.get("batched"):
                saw_batched = True
                assert sp.attrs["batch_size"] >= 2
    assert saw_batched  # the burst actually exercised the coalesced path


def test_sharded_batch_matches_solo(lubm_kb):
    """The sharded fan-out under the runtime: batched == solo answers."""
    from repro.core.shard import ShardedKB

    _, raw = lubm_kb
    skb = ShardedKB.build(raw, n_shards=2)
    qs = [PAPER_QUERIES["Q1"], PAPER_QUERIES["Q3"]]
    rt = ServingRuntime(skb, modes=("litemat",), n_workers=1,
                        batch_window_s=0.05, max_batch=8)
    with rt:
        solo = [rt.serve(q) for q in qs]
        outs = _burst(rt, [qs[i % 2] for i in range(6)])
    assert all(o.ok for o in solo + outs)
    for i, o in enumerate(outs):
        assert o.answers == solo[i % 2].answers


# -- fault isolation ---------------------------------------------------------


def test_batch_member_fault_does_not_poison_batchmates(lubm_kb):
    """One member hitting the serving.execute fault gate retries ALONE;
    every batchmate still answers ok from the shared dispatch."""
    K, _ = lubm_kb
    qs = list(PAPER_QUERIES.values())
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1,
                        batch_window_s=0.05, max_batch=8, max_retries=2)
    with rt:
        expected = [rt.serve(q) for q in qs]
        with faults.inject() as inj:
            inj.arm("serving.execute", exc=faults.FaultError, after=0,
                    times=1)  # exactly one gate check faults
            outs = _burst(rt, [qs[i % len(qs)] for i in range(8)])
            assert inj.fired("serving.execute") == 1
    assert all(o.ok for o in outs)
    for i, o in enumerate(outs):
        assert o.answers == expected[i % len(qs)].answers


def test_whole_batch_failure_degrades_to_solo(lubm_kb):
    """A batch-level execution error falls every member back to its own
    retry ladder — outcomes stay ok, nothing leaks the batch exception."""
    K, _ = lubm_kb
    qs = list(PAPER_QUERIES.values())
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1,
                        batch_window_s=0.05, max_batch=8)
    with rt:
        expected = [rt.serve(q) for q in qs]
        boom = {"armed": True}
        orig = rt.registry.pin

        def bad_pin(*a, **kw):
            pin = orig(*a, **kw)
            if boom.pop("armed", None):
                class _BadPin:
                    version = pin.version
                    stale = pin.stale

                    def query_batch(self, *a, **kw):
                        raise RuntimeError("injected batch crash")

                    def release(self):
                        pin.release()
                return _BadPin()
            return pin

        rt.registry.pin = bad_pin
        try:
            outs = _burst(rt, [qs[i % len(qs)] for i in range(8)])
        finally:
            rt.registry.pin = orig
    assert all(o.ok for o in outs)
    for i, o in enumerate(outs):
        assert o.answers == expected[i % len(qs)].answers
    assert rt.metrics.counter_value("serving/batch_fallback",
                                    reason="batch_error") >= 1


# -- pagination --------------------------------------------------------------


def test_page_union_equals_unpaginated(lubm_kb):
    K, _ = lubm_kb
    rt = ServingRuntime(K, modes=("litemat",), n_workers=2)
    with rt:
        for q in (PAPER_QUERIES["Q1"], PAPER_QUERIES["Q3"]):
            full = rt.serve(q)
            page = rt.serve(q, page_size=7)
            assert page.ok and page.total == len(full.answers)
            got = list(page.answers)
            versions = {page.version}
            while page.cursor is not None:
                assert isinstance(page.cursor, Cursor)
                page = rt.serve(q, cursor=page.cursor)
                assert page.ok
                got += list(page.answers)
                versions.add(page.version)
            assert len(versions) == 1  # every page pinned the same version
            assert len(got) == len(set(got))  # stable order: no dup rows
            assert set(got) == full.answers


def test_cursor_repins_same_version_or_reports_stale(lubm_kb):
    _, raw = lubm_kb
    K = KnowledgeBase.build(raw)  # private KB: this test moves the store
    s, p, o = (np.asarray(raw.s), np.asarray(raw.p), np.asarray(raw.o))
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1)
    with rt:
        q = PAPER_QUERIES["Q1"]
        first = rt.serve(q, page_size=5)
        assert first.ok and first.cursor is not None and not first.stale
        # unchanged store: page 2 re-pins the exact version, not-stale
        second = rt.serve(q, cursor=first.cursor)
        assert second.ok and second.version == first.version
        assert not second.stale

        # the store moves and the old version is retired (no refs held):
        # the continuation degrades to a fresh pin tagged stale
        rt.insert((s[:32], p[:32], o[:32]), auto_compact=False)
        assert first.version not in rt.registry.live_versions()
        third = rt.serve(q, cursor=second.cursor)
        assert third.ok and third.stale
        assert third.version != first.version
    assert rt.metrics.counter_value("snapshot/pin_path",
                                    path="cursor_miss") >= 1


# -- server kinds under the runtime ------------------------------------------


def test_server_fanout_under_runtime(lubm_kb):
    """class_members / class_prop_join ride the runtime's queue, batch by
    concatenation, and match the direct QueryServer answers."""
    from repro.serving.engine import QueryServer

    K, _ = lubm_kb
    srv = QueryServer(K, topk=32)
    names = ["Professor", "Student", "Department", "Chair"]
    want_counts, _ = srv.class_members(names)
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1,
                        batch_window_s=0.05, max_batch=8, server_topk=32)
    with rt:
        out = rt.class_members(names)
        assert out.ok and out.version is not None
        assert np.array_equal(out.answers[0], want_counts)
        # a burst of single-class requests coalesces into one dispatch and
        # still splits the planes back per request
        futs = [rt.submit_class_members([n]) for n in names]
        outs = [f.result() for f in futs]
        assert all(o.ok for o in outs)
        for n, o, want in zip(names, outs, want_counts):
            assert int(o.answers[0][0]) == int(want), n
        jn = rt.class_prop_join(["Professor"], ["worksFor"])
        want_j, _ = srv.class_prop_join(["Professor"], ["worksFor"])
        assert jn.ok and int(jn.answers[0][0]) == int(want_j[0])


# -- runtime bugfix sweep ----------------------------------------------------


def test_start_is_race_free(lubm_kb):
    """S1 regression: concurrent first submits must spawn ONE worker pool."""
    K, _ = lubm_kb
    rt = ServingRuntime(K, modes=("litemat",), n_workers=2)
    barrier = threading.Barrier(8)

    def hammer():
        barrier.wait()
        rt.start()

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert len(rt._workers) == 2
    finally:
        rt.stop()
    assert rt._workers == []


def test_shed_trace_closes_queue_span(lubm_kb):
    """S2 regression: a shed request's queue span must finish — its trace
    exports without the validator's leaked-span rejection."""
    K, _ = lubm_kb
    tracer = Tracer()
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1, max_queue=1,
                        max_batch=1, tracer=tracer)
    with rt:
        with faults.inject() as inj:
            inj.arm("serving.execute", exc=None, delay_s=0.2, times=2)
            futs = [rt.submit(PAPER_QUERIES["Q1"]) for _ in range(8)]
            outs = [f.result() for f in futs]
    shed = [o for o in outs if o.status == "shed"]
    assert shed, "queue of 1 under a blocked worker must shed"
    by_id = {t.trace_id: t for t in tracer.finished_traces()}
    for o in shed:
        tr = by_id[o.trace_id]
        assert validate_trace(tr.to_dict()) == []
        (span,) = tr.find("serving.queue")
        assert span.t1 >= 0 and not span.attrs.get("dangling")


def test_validator_rejects_leaked_span():
    """The tightened invariant itself: a non-root span left open at
    finish_trace is marked dangling and fails validation."""
    tracer = Tracer()
    tr = tracer.new_trace()
    root = tracer.start_root(tr, "serving.request")
    tr.new_span("serving.queue", root.span_id, {})  # never finished
    tracer.finish_trace(tr)
    errors = validate_trace(tr.to_dict())
    assert any("leaked span" in e for e in errors)


def test_latency_stats_is_bounded_state(lubm_kb):
    """S3 regression: latency_stats derives from the registry histogram —
    no per-request list grows on the runtime."""
    K, _ = lubm_kb
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1)
    with rt:
        for _ in range(4):
            assert rt.serve(PAPER_QUERIES["Q1"]).ok
    assert not hasattr(rt, "_latencies")
    stats = rt.latency_stats()
    assert stats["n"] == 4
    assert stats["p50_ms"] > 0 and stats["p99_ms"] >= stats["p50_ms"]
    assert rt.latency_stats(status="error") == dict(n=0)


# -- planner feedback (S4) ---------------------------------------------------


def test_observed_selectivity_flips_inl_decision(lubm_kb):
    """Observations — keyed by (sig, probe-constant bucket) — both enable
    and VETO the INL conversion for exactly their own probe side, while a
    different bucket's observation (the old aliasing) is never consulted."""
    K, _ = lubm_kb
    eng = _fresh_engine(K)
    q4 = PAPER_QUERIES["Q4"]
    planned = eng._plan(q4, None)
    sigs, caps, buckets = planned[0], planned[2], planned[8]
    (j,) = [i for i, s in enumerate(sigs) if s.strategy == "inl"]
    inl_sig, base_cap, inl_bucket = sigs[j], caps[j], buckets[j]

    # a probe-side estimate too big for the heuristic: no conversion
    eng.inl_factor = 64
    sigs2, *_ = eng._plan(q4, None)
    assert not any(s.strategy == "inl" for s in sigs2)

    # one observation of the probe's true (tiny) output flips it back on:
    # observed_rows * factor undercuts the merge-side count
    store_n = max(eng.view.n, 1)
    eng.observed_selectivity[(inl_sig, inl_bucket)] = 10 / store_n
    sigs3, _, caps3, *_ = eng._plan(q4, None)
    (k,) = [i for i, s in enumerate(sigs3) if s.strategy == "inl"]
    assert sigs3[k] == inl_sig
    # ... and the capacity tracks the observation, not the est*32 guess
    assert caps3[k] < base_cap

    # a HUGE observation under a DIFFERENT probe-constant bucket (another
    # probe side that happens to share this sig — Q3's Professors vs Q4's
    # Chairs) is simply not consulted: the heuristic conversion stands
    eng.inl_factor = 8
    eng.observed_selectivity.clear()
    eng.observed_selectivity[(inl_sig, ("other-probe",))] = 1.0
    sigs4, *_ = eng._plan(q4, None)
    assert any(s.strategy == "inl" for s in sigs4)

    # ... while the SAME bucket's huge observation VETOES the conversion
    # the heuristic would have made — the regression the bare-sig keying
    # made impossible (an aliased store could only ever turn INL on)
    eng.observed_selectivity[(inl_sig, inl_bucket)] = 1.0
    sigs5, *_ = eng._plan(q4, None)
    assert not any(s.strategy == "inl" for s in sigs5)

    # the flipped plan answers identically to the oracle
    eng.inl_factor = 64
    eng.observed_selectivity.clear()
    eng.observed_selectivity[(inl_sig, inl_bucket)] = 10 / store_n
    rows, _ = eng.run(q4)
    got = {tuple(r) for r in rows.tolist()}
    assert got == K.answers(q4, mode="litemat")


def test_batch_caps_observation_shrinks_and_grows(lubm_kb):
    """Batched capacity unification: complete per-member evidence lets the
    observed floor SHRINK an over-provisioned cap (previously impossible
    under sig aliasing); partial evidence stays grow-only."""
    K, _ = lubm_kb
    eng = _fresh_engine(K)
    planned = eng._plan(PAPER_QUERIES["Q1"], None)
    store_n = max(eng.view.n, 1)
    key0 = (planned[0][0], planned[8][0])

    # an over-provisioned member: planner caps inflated 16x
    p_big = (planned[0], planned[1], [c * 16 for c in planned[2]],
             planned[3] * 16, *planned[4:])
    caps_big, _ = eng._batch_caps([p_big])
    assert caps_big == p_big[2]  # no observations: planner caps stand

    # complete evidence (the only member is observed): the tiny observed
    # floor REPLACES the inflated cap — the capacity shrinks
    eng.observed_selectivity[key0] = 1 / store_n
    caps_shrunk, _ = eng._batch_caps([p_big])
    assert caps_shrunk[0] < caps_big[0]
    assert caps_shrunk[0] == eng._bucket(int(1 * eng.slack) + 16)

    # a huge observation raises the cap to its floor (growth still works)
    caps0, _ = eng._batch_caps([planned])
    eng.observed_selectivity[key0] = (caps0[0] * 8) / store_n
    caps1, join1 = eng._batch_caps([planned])
    assert caps1[0] > caps0[0]
    assert join1 >= max(caps1)

    # partial evidence: a second member under an UNOBSERVED bucket blocks
    # the shrink — the unified cap may only grow past the planner max
    eng.observed_selectivity[key0] = 1 / store_n
    p_other = (*planned[:8],
               tuple(("unobserved",) for _ in planned[8]))
    caps_mixed, _ = eng._batch_caps([p_big, p_other])
    assert caps_mixed[0] == max(p_big[2][0], planned[2][0])


def test_engine_run_batch_matches_run(lubm_kb):
    """Engine-level batching: dedupe + grouped dispatch returns the same
    rows as per-request run() for a mixed same/different-signature load."""
    K, _ = lubm_kb
    for mode in ("litemat", "full", "rewrite"):
        eng = _fresh_engine(K, mode)
        qs = list(PAPER_QUERIES.values())
        reqs = [(qs[i % len(qs)], None) for i in range(9)]
        outs = eng.run_batch(reqs)
        assert len(outs) == len(reqs)
        for (q, _), (rows, _) in zip(reqs, outs):
            want = {tuple(r) for r in eng.run(q)[0].tolist()}
            assert {tuple(r) for r in rows.tolist()} == want, mode
