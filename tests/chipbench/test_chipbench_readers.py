"""The latency readers, on a window written by hand."""
import pytest

from chipbench import harness, traffic

# (template, latency in ms): ten fast queries, five slow ones, one failure
LAT = [("Q1", 10.0 + i) for i in range(10)] + [("Q9", 1000.0 + i)
                                               for i in range(5)]


def _run():
    run = harness.Run(cell="test", seconds=44.0, window_close=100.0)
    for i, (t, ms) in enumerate(LAT):
        run.requests.append(harness.Request(0, traffic.Query(t, ()), i,
                                            i + ms / 1e3, "ok", 0.0, ms / 1e3))
    run.requests.append(harness.Request(0, traffic.Query("Q4", ()), 20, 99,
                                        "error", 0.0, 0.0))
    return run


@pytest.mark.parametrize("metric, want", [
    ("query_p50_ms", 17.0),  # the 8th of 15 latencies
    ("query_p65_ms", 117.1),  # a tenth of the way from the 10th to the 11th
    ("slowest_template_ms", 1002.0),  # Q9's median
])
def test_latency_readers(metric, want):
    got = harness.load_reader(harness.CHECKOUT, metric)(_run())
    assert got == pytest.approx(want)
