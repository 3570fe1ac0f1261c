"""The trace reduction, on a small trace recorded on one TPU v5e: three
requests, each a sort program and a matrix product, with harness spans
and host sleeps between them."""
from pathlib import Path

import pytest

from chipbench import trace_reduce

TRACE = Path(__file__).parent / "data" / "small.xplane.pb"
SPANS = ("client.request", "query.execute", "query.plan")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce(TRACE, "chipbench.window", SPANS)


def test_window_and_busy_time(summary):
    assert summary.n_devices == 1
    assert summary.window_s == pytest.approx(0.132124508)
    # three sorts of 64K rows and three 512x512 products: well under 1 ms
    assert 0 < summary.busy_s < 1e-3
    assert summary.busy_s < summary.window_s


def test_top_device_ops(summary):
    times = [t for _, t in summary.device_ops]
    assert times == sorted(times, reverse=True)
    assert len(summary.device_ops) == trace_reduce.TOP
    assert any(name.startswith("sort") for name, _ in summary.device_ops)
    assert sum(times) <= summary.busy_s * 1.0001  # ops do not overlap here


def test_idle_gaps_named_by_the_open_span(summary):
    gaps = summary.idle_gaps
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    # the three 30 ms host pauses inside each request come first
    assert [n for n, _ in gaps[:3]] == ["client.request"] * 3
    assert all(0.025 < g < 0.04 for _, g in gaps[:3])
    assert {n for n, _ in gaps} <= set(SPANS) | {"no span"}


def test_op_names_are_short():
    name = ("%while.119 = (s32[]{:T(128)}, s32[524288]{0:T(1024)S(1)}) "
            "while(...), condition=%cond, body=%body")
    short = trace_reduce.op_name(name)
    assert short.startswith("while.119 (s32[]") and len(short) <= 96
