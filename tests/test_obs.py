"""Observability subsystem: metrics registry, tracer, serving trace contract.

Three layers of coverage:

  * unit behavior of the instruments — counter atomicity under threads,
    histogram sketch accuracy and windowed summaries, the kernel
    pass-counter race fix, the tracer's span tree / ring bound / no-op
    off path, the hand-rolled trace schema validator;
  * the planner introspection surface (``QueryEngine.explain`` +
    observed-selectivity capture);
  * the serving contract (the tentpole's acceptance bar): EVERY submitted
    request — ok, retried, stale-degraded, deadline-missed, shed — yields
    exactly one schema-valid trace whose structure matches its Outcome
    (pin span present, attempt spans == retries + 1, queue_s + exec_s ==
    latency_s), under the tests/test_faults.py fault matrix.
"""
import json
import threading

import numpy as np
import pytest

import jax

from repro.core.engine import KnowledgeBase, PAPER_QUERIES
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.obs.export import export_traces, validate, validate_trace
from repro.obs.metrics import (MetricsRegistry, REGISTRY, window_summary)
from repro.obs.trace import Tracer, activate
from repro.serving.runtime import ServingRuntime
from repro.testing import faults
from repro.testing.faults import FaultCrash, FaultError

Q1, Q4 = PAPER_QUERIES["Q1"], PAPER_QUERIES["Q4"]


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.uninstall()


@pytest.fixture(scope="module")
def obs_kb():
    """Private KB for the tests that INSERT through the runtime (and crash
    its flushes mid-way): the session-scoped ``lubm_kb`` is shared with
    every later test file and must stay pristine."""
    from repro.rdf.generator import generate_lubm

    raw = generate_lubm(n_universities=1, seed=7)
    return KnowledgeBase.build(raw), raw


# -- metrics instruments ------------------------------------------------------

def test_counter_increments_are_atomic_under_threads():
    reg = MetricsRegistry()
    c = reg.counter("t/hits", kind="x")

    def worker():
        for _ in range(2000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 16000
    assert reg.counter_value("t/hits", kind="x") == 16000
    assert reg.counter_value("t/hits", kind="untouched") == 0


def test_pass_counters_thread_safe_and_mirrored():
    """Satellite fix: ops.pass_counters bumps were racy dict +=."""
    before = ops.reset_pass_counters()
    assert set(before) == set(ops.pass_counters)
    assert all(v == 0 for v in ops.pass_counters.values())
    mirror0 = REGISTRY.counter_value("kernels/passes", kind="merge")

    def worker():
        for _ in range(500):
            ops._bump_pass("merge")

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ops.pass_counters["merge"] == 4000
    assert (REGISTRY.counter_value("kernels/passes", kind="merge")
            - mirror0) == 4000
    snap = ops.reset_pass_counters()
    assert snap["merge"] == 4000  # snapshot semantics preserved
    assert ops.pass_counters["merge"] == 0


def test_histogram_sketch_accuracy_and_summary():
    reg = MetricsRegistry()
    h = reg.histogram("t/lat")
    rng = np.random.default_rng(0)
    xs = rng.uniform(1.0, 1000.0, size=2000)
    for x in xs:
        h.observe(float(x))
    s = h.summary()
    assert s["n"] == 2000
    assert s["min"] == float(xs.min()) and s["max"] == float(xs.max())
    assert abs(s["mean"] - xs.mean()) < 1e-6
    # log-bucket sketch: <=~4.5% value error, allow slack for rank error
    assert abs(s["p50"] - np.percentile(xs, 50)) / np.percentile(xs, 50) < 0.1
    assert abs(s["p99"] - np.percentile(xs, 99)) / np.percentile(xs, 99) < 0.1
    assert reg.histogram("t/empty").summary() == dict(n=0)


def test_window_summary_excludes_prior_observations():
    reg = MetricsRegistry()
    h = reg.histogram("t/win")
    for _ in range(100):
        h.observe(1.0)  # warmup epoch: all small
    before = h.state()
    for _ in range(50):
        h.observe(100.0)  # measured window: all large
    w = window_summary(h, before)
    assert w["n"] == 50
    assert abs(w["mean"] - 100.0) < 1e-9
    assert w["p50"] > 50.0  # warmup's 1.0s must not drag the median down
    assert h.summary()["p50"] < 50.0  # ...though they dominate the total
    assert window_summary(h, h.state()) == dict(n=0)


def test_registry_snapshot_is_json_ready():
    reg = MetricsRegistry()
    reg.counter("a/ops", kind="merge").inc(3)
    reg.gauge("a/depth").set(7)
    reg.histogram("a/lat", status="ok").observe(0.25)
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["counters"]["a/ops{kind=merge}"] == 3
    assert snap["gauges"]["a/depth"] == 7
    assert snap["histograms"]["a/lat{status=ok}"]["n"] == 1


# -- tracer -------------------------------------------------------------------

def test_span_tree_parenting_and_error_capture():
    tracer = Tracer()
    tr = tracer.new_trace()
    root = tracer.start_root(tr, "serving.request", mode="litemat")
    with activate(root):
        with obs_trace.span("snapshot.pin", version=3):
            with obs_trace.span("serving.execute"):
                obs_trace.event("marker", k=1)
        with pytest.raises(ValueError):
            with obs_trace.span("boom"):
                raise ValueError("no")
    tracer.finish_trace(tr)

    d = tr.to_dict()
    assert validate_trace(d) == []
    by_name = {s["name"]: s for s in d["spans"]}
    assert by_name["snapshot.pin"]["parent_id"] == root.span_id
    assert by_name["serving.execute"]["parent_id"] == \
        by_name["snapshot.pin"]["span_id"]
    assert by_name["serving.execute"]["events"][0]["name"] == "marker"
    assert "ValueError" in by_name["boom"]["attrs"]["error"]
    assert all(s["t1"] >= s["t0"] for s in d["spans"])


def test_span_is_noop_without_active_trace():
    # no activate() anywhere: instrumented code must run untraced for free
    with obs_trace.span("anything", x=1) as sp:
        sp.set_attr(y=2)
        sp.add_event("e")
    obs_trace.event("nothing")
    assert obs_trace.current_span() is None


def test_tracer_ring_is_bounded():
    tracer = Tracer(max_traces=4)
    for _ in range(7):
        tr = tracer.new_trace()
        tracer.start_root(tr, "r")
        tracer.finish_trace(tr)
    assert len(tracer.finished_traces()) == 4
    assert tracer.dropped == 3
    ids = [t.trace_id for t in tracer.finished_traces()]
    assert ids == sorted(ids)  # oldest dropped, order kept


def test_validator_catches_malformed_traces():
    tracer = Tracer()
    tr = tracer.new_trace()
    tracer.start_root(tr, "r")
    good = tr.to_dict()
    assert validate_trace(good) == []

    bad = json.loads(json.dumps(good))
    bad["spans"][0]["parent_id"] = 42  # no root anymore + dangling parent
    assert validate_trace(bad)

    bad = json.loads(json.dumps(good))
    bad["spans"][0]["t1"] = bad["spans"][0]["t0"] - 1.0
    assert any("t1 < t0" in e for e in validate_trace(bad))

    bad = json.loads(json.dumps(good))
    del bad["spans"][0]["name"]
    assert any("missing required key" in e for e in validate_trace(bad))

    assert validate(True, {"type": "integer"})  # bool is not an integer


# -- planner introspection ----------------------------------------------------

def test_explain_reports_plan_and_observed_rows(lubm_kb):
    K, _ = lubm_kb
    eng = K.engine("litemat")
    rows, sel = eng.run(Q4)
    info = eng.explain(Q4)
    assert info["mode"] == "litemat"
    assert info["n_result_rows"] == rows.shape[0]
    assert len(info["patterns"]) == len(Q4)
    for p in info["patterns"]:
        assert p["strategy"] in ("slice", "scan", "inl")
        assert p["estimated_rows"] >= 0
        assert 0.0 <= p["selectivity"] <= 1.0
    # observed selectivities land in the process registry as gauges
    gauges = REGISTRY.gauges_with_prefix("planner/selectivity")
    assert gauges  # at least one strategy/store combination recorded
    assert eng.observed_selectivity  # per-signature capture for the planner


# -- the serving trace contract (tentpole acceptance) -------------------------

def _traces_by_id(tracer):
    return {t.trace_id: t for t in tracer.finished_traces()}


def test_every_request_yields_one_wellformed_trace(obs_kb):
    """ok / retried / stale / deadline / shed requests under the fault
    matrix: one schema-valid trace each, structure matching the Outcome."""
    K, raw = obs_kb
    s, p, o = np.asarray(raw.s), np.asarray(raw.p), np.asarray(raw.o)
    tracer = Tracer()
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1,
                        pin_lock_timeout_s=0.05, max_queue=64,
                        tracer=tracer)
    outs = []
    with rt:
        outs.append(rt.serve(Q1))  # clean fast-path pin
        with faults.inject() as inj:
            # two transient execute failures -> retries == 2, then ok
            inj.arm("serving.execute", exc=FaultError, times=2)
            outs.append(rt.serve(Q1))
        with faults.inject() as inj:
            # crash the writer's publish AND the reader's own fresh-capture
            # attempt: the reader degrades to the stale published snapshot
            inj.arm("engine.flush_mat", exc=FaultCrash, times=2)
            rt.insert((s[:32], p[:32], o[:32]), auto_compact=False)
            outs.append(rt.serve(Q1))
        outs.append(rt.serve(Q1, deadline_s=0.0))  # preempted at dequeue
        with faults.inject() as inj:
            # delay-only fault pins the single worker down long enough for
            # the bounded queue to fill: later submits shed at admission
            inj.arm("serving.execute", exc=None, delay_s=0.3, times=0)
            slow = ServingRuntime(K, modes=("litemat",), n_workers=1,
                                  max_queue=1, tracer=tracer)
            with slow:
                futs = [slow.submit(Q1) for _ in range(6)]
                outs.extend(f.result() for f in futs)

    assert [o.status for o in outs[:4]] == ["ok", "ok", "ok", "deadline"]
    assert outs[1].retries == 2
    assert outs[2].stale is True
    assert any(o.status == "shed" for o in outs[4:])
    assert rt.stats["retries"] == 2 and rt.stats["stale_served"] == 1
    assert rt.registry.stats["stale_pins"] >= 1

    by_id = _traces_by_id(tracer)
    assert len(by_id) == len(outs)  # exactly one trace per request
    for out in outs:
        tr = by_id[out.trace_id]
        d = tr.to_dict()
        assert validate_trace(d) == [], d["trace_id"]
        root = d["spans"][0]
        assert root["name"] == "serving.request"
        assert root["attrs"]["status"] == out.status
        assert root["attrs"]["retries"] == out.retries
        names = [s["name"] for s in d["spans"]]
        assert "serving.queue" in names
        if out.status == "shed":
            # rejected at admission: no execution spans ever open
            assert "snapshot.pin" not in names
            assert "serving.execute" not in names
        elif out.status == "ok":
            assert "snapshot.pin" in names and "serving.execute" in names
            assert len(tr.find("serving.attempt")) == out.retries + 1
            pin_attrs = tr.find("snapshot.pin")[-1].attrs
            assert pin_attrs["version"] == out.version
            assert pin_attrs["stale"] == out.stale
        # (deadline_s=0.0 preempts before the first attempt: no pin span,
        # just the deadline_preempt event on the root)
        # timing split: exact by construction
        assert abs(out.queue_s + out.exec_s - out.latency_s) < 1e-9


def test_stale_degradation_event_recorded(obs_kb):
    K, raw = obs_kb
    s, p, o = np.asarray(raw.s), np.asarray(raw.p), np.asarray(raw.o)
    tracer = Tracer()
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1,
                        pin_lock_timeout_s=0.05, tracer=tracer)
    with rt:
        with faults.inject() as inj:
            inj.arm("engine.flush_mat", exc=FaultCrash, times=2)
            rt.insert((s[:16], p[:16], o[:16]), auto_compact=False)
            out = rt.serve(Q1)
    assert out.stale
    tr = _traces_by_id(tracer)[out.trace_id]
    events = [e["name"] for sp in tr.spans for e in sp.events]
    assert "stale_degraded" in events


def test_trace_export_roundtrip(tmp_path, lubm_kb):
    K, _ = lubm_kb
    tracer = Tracer()
    rt = ServingRuntime(K, modes=("litemat",), n_workers=2, tracer=tracer)
    with rt:
        for _ in range(5):
            assert rt.serve(Q1).ok
    path = tmp_path / "traces.json"
    n = export_traces(tracer, str(path))
    assert n == 5
    doc = json.loads(path.read_text())
    assert doc["dropped"] == 0
    for trace in doc["traces"]:
        assert validate_trace(trace) == []


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="shard_map path needs >1 XLA device")
def test_shard_map_fallback_recorded_in_trace(lubm_kb):
    from repro.core.shard import ShardedKB

    _, raw = lubm_kb
    skb = ShardedKB.build(raw, n_shards=2)
    eng = skb.engine("litemat")
    expected = skb.answers(Q1)  # also warms plans/stacks

    tracer = Tracer()
    tr = tracer.new_trace()
    root = tracer.start_root(tr, "test")
    faults0 = eng.cache_stats["shard_map_faults"]
    with faults.inject() as inj:
        inj.arm("shard.shard_map", exc=FaultError, times=1)
        with activate(root):
            rows, sel = eng.run(Q1)
    tracer.finish_trace(tr)
    assert {tuple(r) for r in rows.tolist()} == expected
    assert eng.cache_stats["shard_map_faults"] == faults0 + 1

    d = tr.to_dict()
    assert validate_trace(d) == []
    dispatches = [s for s in d["spans"] if s["name"] == "shard.dispatch"]
    paths = [s["attrs"].get("path") for s in dispatches]
    assert "shard_map" in paths and "loop" in paths  # degraded mid-request
    sm = next(s for s in dispatches if s["attrs"]["path"] == "shard_map")
    assert "error" in sm["attrs"]  # the injected fault is on the span
    events = [e["name"] for s in d["spans"] for e in s["events"]]
    assert "shard_map_fallback" in events


def test_snapshot_registry_stats_view(lubm_kb):
    K, _ = lubm_kb
    from repro.core.snapshot import SnapshotRegistry

    reg = SnapshotRegistry(K, modes=("litemat",))
    reg.publish()
    pin = reg.pin()
    try:
        st = reg.stats
        assert st["publishes"] >= 1 and st["pins"] == 1
        assert reg.metrics.gauge_value("snapshot/pinned_refs") == 1
    finally:
        pin.release()
    assert reg.metrics.gauge_value("snapshot/pinned_refs") == 0


# -- program spans on the profiler's clock ------------------------------------

def _host_events(logdir) -> list:
    """(name, start_ns, end_ns) of every host event in a profiler trace."""
    from pathlib import Path

    (path,) = Path(logdir).rglob("*.xplane.pb")
    pd = jax.profiler.ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_program_span_is_a_host_event_nested_in_the_profiler_trace(tmp_path):
    tracer = Tracer()
    tr = tracer.new_trace()
    root = tracer.start_root(tr, "serving.request")
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("outer"), activate(root):
            with obs_trace.span("query.plan"):
                with obs_trace.span("query.plan.count"):
                    pass
    tracer.finish_trace(tr)
    ev = {n: (s, e) for n, s, e in _host_events(tmp_path)}
    assert {"outer", "query.plan", "query.plan.count"} <= set(ev)
    # the root was opened on no thread's stack: memory only
    assert "serving.request" not in ev
    for inner, outer in (("query.plan", "outer"),
                         ("query.plan.count", "query.plan")):
        assert ev[outer][0] <= ev[inner][0] <= ev[inner][1] <= ev[outer][1]


def test_program_spans_keep_their_spacing_in_the_profiler_trace(tmp_path):
    import gc
    import time

    tracer = Tracer()
    tr = tracer.new_trace()
    root = tracer.start_root(tr, "serving.request")
    gc.disable()  # no collector pause between a span and its annotation
    try:
        with jax.profiler.trace(str(tmp_path)), activate(root):
            with obs_trace.span("snapshot.pin"):
                pass
            time.sleep(0.05)
            with obs_trace.span("query.plan"):
                pass
    finally:
        gc.enable()
    tracer.finish_trace(tr)
    ev = {n: s for n, s, _ in _host_events(tmp_path)}
    (pin,), (plan,) = tr.find("snapshot.pin"), tr.find("query.plan")
    in_memory = plan.t0 - pin.t0
    assert in_memory >= 0.05
    assert abs((ev["query.plan"] - ev["snapshot.pin"]) * 1e-9
               - in_memory) < 1e-3


def test_span_builds_no_annotation_without_a_tracer(monkeypatch):
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Counting)
    assert obs_trace.span("query.plan") is obs_trace._NOOP
    with obs_trace.span("query.plan"):
        pass
    assert built == []
    tracer = Tracer()
    tr = tracer.new_trace()
    with activate(tracer.start_root(tr, "serving.request")):
        with obs_trace.span("query.plan"):
            pass
    assert built == ["query.plan"]


def test_runtime_traces_into_the_profile_tracer_while_profiling(
        lubm_kb, tmp_path, monkeypatch):
    K, _ = lubm_kb
    monkeypatch.setattr(obs_trace, "PROFILE_TRACER", Tracer())
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1)
    with rt:
        assert rt.serve(Q1).trace_id is None  # no tracer, no profiler
        with jax.profiler.trace(str(tmp_path)):
            out = rt.serve(Q1)
    (tr,) = obs_trace.PROFILE_TRACER.finished_traces()
    assert out.trace_id == tr.trace_id and tr.root.name == "serving.request"
    assert validate_trace(tr.to_dict()) == []
    host = {n for n, _, _ in _host_events(tmp_path)}
    assert {"serving.attempt", "snapshot.pin", "query.plan",
            "query.dispatch", "serving.answers"} <= host


def test_solo_request_spans_name_each_layer(lubm_kb):
    """Parenting of the served path's spans, and one ``query.plan.count``
    span and ``query/plan_syncs`` increment per planner round trip."""
    K, _ = lubm_kb
    tracer = Tracer()
    syncs0 = REGISTRY.counter_value("query/plan_syncs")
    rt = ServingRuntime(K, modes=("litemat",), n_workers=1,
                        use_index=False, tracer=tracer)
    with rt:
        out = rt.serve(Q4)
    assert out.ok
    tr = _traces_by_id(tracer)[out.trace_id]
    by_id = {s.span_id: s for s in tr.spans}

    def parent(name):
        return {by_id[s.parent_id].name for s in tr.find(name)}

    assert parent("query.plan") == {"serving.execute"}
    assert parent("query.plan.count") == {"query.plan"}
    assert parent("query.execute") == {"serving.execute"}
    for name in ("query.dispatch", "query.device_wait", "query.fetch"):
        assert parent(name) == {"query.execute"}, name
    assert parent("serving.answers") == {"serving.execute"}
    counts = tr.find("query.plan.count")
    assert counts  # the scan-only store plans every pattern by counting
    assert REGISTRY.counter_value("query/plan_syncs") - syncs0 == len(counts)


def test_served_executables_and_kernels_have_stable_names(lubm_kb):
    from repro.kernels import stream_compact

    K, _ = lubm_kb
    eng = K.engine("litemat", use_index=False)
    eng.run(Q1)
    eng.run_batch([(Q4, None), (PAPER_QUERIES["Q4"], ("?x",))])
    names = set()
    for key, fn in eng._exec_cache.items():
        fn = getattr(fn, "__wrapped__", fn)
        names.add(fn.__name__)
    assert {"query_exec", "plan_count"} <= names
    planned = eng._plan(Q1, None)
    sigs, dyns, caps, join_cap, sel, stores = planned[:6]
    fn = eng._executable(("exec", eng.mode, sigs, tuple(caps), join_cap, sel),
                         sigs, tuple(caps), join_cap, sel)
    assert "@jit_query_exec" in fn.__wrapped__.lower(stores, dyns).as_text()
    bfn = eng._batch_executable(("bexec", eng.mode, sigs, tuple(caps),
                                 join_cap, sel, 2), sigs, tuple(caps),
                                join_cap, sel)
    stacked = jax.tree_util.tree_map(lambda x: jax.numpy.stack([x, x]), dyns)
    assert "@jit_query_exec_batch" in \
        bfn.__wrapped__.lower(stores, stacked).as_text()
    mask = jax.numpy.zeros(2048, jax.numpy.int32)
    jaxpr = jax.make_jaxpr(lambda m: stream_compact.stream_compact_pallas(
        m, block=1024, interpret=True))(mask)
    assert "name=stream_compact" in str(jaxpr)
