"""The traffic generator: schedules follow from the seed alone."""
import itertools

from chipbench import lubm, traffic
from chipbench.harness import load_cell


def _pools(cell):
    data = lubm.generate(1, 1, lubm.ontology())
    return traffic.candidates(data, cell.qset, cell.mix["templates"])


def _take(cell, pools, seed, n, client=0):
    return list(itertools.islice(
        traffic.client_stream(cell.mix, cell.qset, pools, seed, client), n))


def test_rounds_are_stratified_and_repeat_for_a_seed(cpu_root):
    cell = load_cell(cpu_root, "tiny.rounds")
    seed = 2**31 + 977  # the driver's seeds pass 32 signed bits
    pools = _pools(cell)
    t = cell.mix["templates"]
    a = _take(cell, pools, seed, 5 * len(t))
    assert a == _take(cell, _pools(cell), seed, 5 * len(t))
    for r in range(5):  # every round holds every template once
        assert sorted(q.template for q in a[r * len(t):(r + 1) * len(t)]) \
            == sorted(t)
    b = _take(cell, pools, seed + 1, 5 * len(t))
    assert a != b


def test_rounds_keep_the_mix_order(cpu_root):
    cell = load_cell(cpu_root, "tiny.rounds")
    t = cell.mix["templates"]
    got = [q.template for q in _take(cell, _pools(cell), 2**33 + 5, 3 * len(t))]
    assert got == 3 * list(t)


def test_seeds_change_only_the_constants(cpu_root):
    cell = load_cell(cpu_root, "tiny.rounds")
    pools = _pools(cell)
    n = 4 * len(cell.mix["templates"])
    a, b = _take(cell, pools, 2**31 + 1, n), _take(cell, pools, 2**31 + 2, n)
    assert [q.template for q in a] == [q.template for q in b]
    assert [q.params for q in a] != [q.params for q in b]
    for qa, qb in zip(a, b):  # a template without constants repeats
        if not cell.qset["templates"][qa.template]["params"]:
            assert qa == qb


def test_warmup_holds_what_the_window_sends_first(cpu_root):
    cell = load_cell(cpu_root, "tiny.rounds")
    pools = _pools(cell)
    warm = traffic.warmup_queries(cell.mix, cell.qset, pools, 12345)
    first = {q.key() for c in range(cell.mix["clients"])
             for q in _take(cell, pools, 12345, traffic.WARMUP_QUERIES,
                            client=c)}
    assert [q.key() for q in warm] == list(dict.fromkeys(q.key() for q in warm))
    assert {q.key() for q in warm} == first


def test_parameters_come_from_the_data(cpu_root):
    cell = load_cell(cpu_root, "tiny.rounds")
    pools = _pools(cell)
    for q in _take(cell, pools, 99, 200):
        params = cell.qset["templates"][q.template]["params"]
        for name, fp in q.params:
            assert fp in set(pools[params[name]].tolist())
