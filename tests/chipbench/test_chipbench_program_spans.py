"""The readers of the program's own spans (chipbench/program_spans.py and
five metrics), on span trees written by hand, and on a traced and an
untraced window of a small cell."""
import importlib

import pytest

from chipbench import harness, program_spans

from chipbench_roots import scratch_root

EPOCH = 1000.0
SPAN_METRICS = ("capacity_retries_per_query", "plan_ms_per_query",
                "device_wait_ms_per_query", "answers_ms_per_query",
                "request_gap_ms_max")


def _tree(tracer, root, children, status="ok"):
    """A finished trace: ``root`` and each child (name, t0, t1, parent
    index into the list, retries) at the given offsets from the epoch."""
    from repro.obs.trace import Trace

    tr = Trace(tracer, f"req-{len(tracer.finished_traces()):06d}")
    spans = [tracer.start_root(tr, "serving.request")]
    spans[0].t0, spans[0].t1 = root
    for name, t0, t1, parent, retries in children:
        sp = tr.new_span(name, spans[parent].span_id, {})
        sp.t0, sp.t1 = t0, t1
        sp.events.extend({"name": "overflow_retry", "t": t0}
                         for _ in range(retries))
        spans.append(sp)
    spans[0].set_attr(status=status)
    tracer.finish_trace(tr)


@pytest.fixture
def window(monkeypatch):
    """Two requests answered inside [10, 20] s after the epoch, and three
    that the readers leave out: one of the warm-up, one failed, one
    answered after the close."""
    from repro.obs import trace

    tracer = trace.Tracer()
    tracer.epoch = EPOCH
    monkeypatch.setattr(trace, "PROFILE_TRACER", tracer)
    # A: uncovered [11.05, 11.1] and [11.9, 12.0]
    _tree(tracer, (11.0, 12.0), [
        ("serving.queue", 11.0, 11.05, 0, 0),
        ("serving.attempt", 11.1, 11.9, 0, 0),
        ("query.plan", 11.15, 11.35, 2, 0),
        ("query.plan.count", 11.2, 11.3, 3, 0),
        ("query.dispatch", 11.4, 11.45, 2, 2),
        ("query.device_wait", 11.45, 11.6, 2, 0),
        ("query.fetch", 11.6, 11.62, 2, 0),
        ("serving.answers", 11.65, 11.75, 2, 0)])
    # B: overlapping children cover [13.0, 13.6]; uncovered [13.6, 14.0]
    _tree(tracer, (13.0, 14.0), [
        ("serving.queue", 13.0, 13.3, 0, 0),
        ("serving.attempt", 13.2, 13.5, 0, 0),
        ("query.plan", 13.25, 13.6, 2, 0),
        ("query.device_wait", 13.3, 13.4, 2, 0)])
    _tree(tracer, (5.0, 6.0), [("query.dispatch", 5.1, 5.2, 0, 5),
                               ("query.plan", 5.2, 5.9, 0, 0)])
    _tree(tracer, (14.0, 15.0), [("query.plan", 14.1, 14.9, 0, 0)],
          status="error")
    _tree(tracer, (19.5, 20.5), [("query.dispatch", 19.6, 19.7, 0, 3)])
    return harness.Run(cell="test", seconds=10.0, window_open=EPOCH + 10,
                       window_close=EPOCH + 20)


@pytest.mark.parametrize("metric, want", [
    ("capacity_retries_per_query", 1.0),  # A's 2 retries over 2 answered
    ("plan_ms_per_query", 275.0),  # (200 + 350) / 2, counts included
    ("device_wait_ms_per_query", 125.0),  # (150 + 100) / 2
    ("answers_ms_per_query", 60.0),  # (20 + 100) / 2
    ("request_gap_ms_max", 400.0),  # B's tail after the merged children
])
def test_span_readers(window, metric, want):
    got = harness.load_reader(harness.CHECKOUT, metric)(window)
    assert got == pytest.approx(want)


def test_answered_trees_are_on_the_harness_clock(window):
    trees = program_spans.answered(window)
    assert [t[0]["t0"] for t in trees] == [EPOCH + 11.0, EPOCH + 13.0]
    assert all(window.window_open <= s["t0"] <= s["t1"] <= window.window_close
               for t in trees for s in t)


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_readers_report_nothing_untraced(monkeypatch, metric):
    from repro.obs import trace

    run = harness.Run(cell="test", seconds=10.0, window_open=0.0,
                      window_close=1e12)
    monkeypatch.setattr(trace, "PROFILE_TRACER", trace.Tracer())
    assert harness.load_reader(harness.CHECKOUT, metric)(run) is None
    # a program that has no such tracer
    monkeypatch.delattr(trace, "PROFILE_TRACER")
    assert harness.load_reader(harness.CHECKOUT, metric)(run) is None


def test_longest_uncovered_merges_overlaps():
    spans = [{"t0": 0.0, "t1": 10.0}, {"t0": 1.0, "t1": 4.0},
             {"t0": 3.0, "t1": 5.0}, {"t0": 8.0, "t1": 12.0},
             {"t0": -2.0, "t1": 0.5}]
    assert program_spans.longest_uncovered(spans) == 3.0  # [5, 8]
    assert program_spans.longest_uncovered(spans[:1]) == 10.0


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A small cell's session; the layer wrappers a traced window installs
    on the program's classes are taken off again afterwards."""
    from repro.obs import trace

    saved = []
    for mod, cls, meth, _ in harness.LAYER_SPANS:
        owner = getattr(importlib.import_module(mod), cls)
        saved.append((owner, meth, owner.__dict__[meth]))
    root = scratch_root(tmp_path_factory.mktemp("spans"), universities=2)
    s = harness.open_session(harness.load_cell(root, "tiny.rounds"),
                             require_tpu=False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "PROFILE_TRACER", trace.Tracer())
        yield s
    s.close()
    for owner, meth, fn in saved:
        setattr(owner, meth, fn)


def test_untraced_window_collects_no_spans(tiny):
    run = harness.measure(tiny, 2**32 + 15, 2.0, trace=False)
    assert tiny.rt.tracer is None
    assert run.completed and program_spans.answered(run) == []


def test_traced_window_reports_the_span_metrics(tiny):
    run = harness.measure(tiny, 2**32 + 16, 2.0, trace=True)
    line = harness.result_line(tiny.cell, run, tiny.dev,
                               {"wrong_answers": {"value": 0, "limit": 0}},
                               trace=True)
    got = line["metrics"]
    assert set(SPAN_METRICS) <= set(got)
    assert len(program_spans.answered(run)) == len(run.completed)
    assert got["plan_ms_per_query"]["value"] > 0
    assert got["device_wait_ms_per_query"]["value"] > 0
    assert got["answers_ms_per_query"]["value"] > 0
    assert got["capacity_retries_per_query"]["value"] >= 0
    assert 0 <= got["request_gap_ms_max"]["value"] < 1e3 * max(
        r.latency_s for r in run.completed)
