"""The check that decides ``correct``: a sound run passes it; the
reference's control in the program's place, and faults planted under the
timed path, fail it.  The chip is skipped; the rest of a run is driven."""
import pytest

from chipbench import harness
from chipbench.reference import ALL_RULES

from chipbench_roots import scratch_root

CONTROL = ("subclass", "subprop")  # no domain or range entailment


@pytest.fixture(scope="module")
def rounds(tmp_path_factory):
    root = scratch_root(tmp_path_factory.mktemp("rounds"))
    s = harness.open_session(harness.load_cell(root, "tiny.rounds"),
                             require_tpu=False)
    yield s
    s.close()


def _checks(s, seed, rules=ALL_RULES, seconds=3.0):
    run = harness.measure(s, seed, seconds)
    decoded = harness.decode(s.K, run.requests, s.cell.qset)
    return run, harness.check(s, run, decoded, rules)


def test_sound_run_is_correct(rounds):
    run, checks = _checks(rounds, 2**31 + 11)
    assert run.requests and checks["wrong_answers"]["value"] == 0
    assert checks["unanswered"]["value"] == 0


def test_control_is_not_correct(rounds):
    run, checks = _checks(rounds, 2**31 + 12, rules=CONTROL)
    assert {r.query.template for r in run.requests} >= {"Q12"}
    assert checks["wrong_answers"]["value"] > checks["wrong_answers"]["limit"]


def test_answer_altered_where_produced(rounds, monkeypatch):
    from repro.core.query import QueryEngine

    run_planned = QueryEngine._run_planned

    def altered(self, *a, **kw):
        rows, sel = run_planned(self, *a, **kw)
        return (rows[1:] if len(rows) else rows), sel

    monkeypatch.setattr(QueryEngine, "_run_planned", altered)
    _, checks = _checks(rounds, 2**31 + 13)
    assert checks["wrong_answers"]["value"] > 0
