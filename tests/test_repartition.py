"""Device-side hash-repartition joins: parity, skew, faults, cursor pins.

The cross-group combine used to all-gather per-shard relations to the host
and fold them there (`combine_groups` + `_host_relation` re-upload).  The
repartition path hashes the join key, exchanges capacity-padded partitions
(all-to-all under shard_map; an axis swap on the emulated dispatch path),
and joins shard-locally — intermediate relations never leave devices.  The
contract pinned here: rows bit-identical to the host fold AND the
single-device engine, zero host re-uploads on the device combine, graceful
degradation to the host fold on exchange faults, and survival of the
`pin_version` cursor path across concurrent retirement.
"""
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.engine import KnowledgeBase, PAPER_QUERIES
from repro.core.query import Pattern
from repro.core.shard import ShardedKB, assert_partitioned
from repro.core.snapshot import SnapshotRegistry
from repro.core.tbox import RDF_TYPE, Ontology
from repro.kernels import ops
from repro.obs.metrics import REGISTRY
from repro.rdf.generator import generate_random_abox
from repro.testing import faults
from repro.testing.faults import FaultCrash, FaultError
from repro.utils.hashing import fingerprint_string

MODES = ("litemat", "full", "rewrite")


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    yield
    faults.uninstall()


@pytest.fixture(scope="module", autouse=True)
def _free_compiled_state():
    """Drop this module's compiled executables when it finishes.

    The parity matrix (4 queries x 3 modes x device/host combine, plus the
    skew and update sweeps) JITs a few hundred executables; leaving them
    resident pushes the process's accumulated XLA state high enough that a
    compile much later in the full tier-1 run can crash the CPU backend.
    Later modules just recompile what they need.
    """
    yield
    import jax

    jax.clear_caches()


def _sel(patterns):
    return tuple(dict.fromkeys(
        v for p in patterns for v in (p.s, p.p, p.o)
        if isinstance(v, str) and v.startswith("?")))


def _repartition_engine(S, mode):
    """Force the device combine on the dispatch-loop path (1-device CI)."""
    eng = S.engine(mode)
    eng.use_shard_map = False
    eng.use_repartition_join = True
    return eng


@pytest.fixture(scope="module")
def sharded_pair(lubm_kb):
    K, raw = lubm_kb
    return K, ShardedKB.build(raw, n_shards=4), raw


# ---------------------------------------------------------------------------
# bit-identical parity: repartition == host fold == single-device engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_repartition_matches_host_fold_and_single(sharded_pair, mode):
    K, S, _ = sharded_pair
    eng = _repartition_engine(S, mode)
    runs0 = eng.cache_stats["repartition_runs"]
    for qn, pats in PAPER_QUERIES.items():
        want, wsel = K.query(pats, mode=mode)
        got, gsel = eng.run(pats)
        assert gsel == wsel and np.array_equal(np.asarray(got), want), (
            mode, qn)
        eng.use_repartition_join = False
        try:
            host, hsel = eng.run(pats)
        finally:
            eng.use_repartition_join = True
        assert hsel == gsel and np.array_equal(np.asarray(host),
                                               np.asarray(got)), (mode, qn)
    # at least one paper query per mode is multi-group (Q4's ?y join), so
    # the device combine must actually have run — not silently degraded
    assert eng.cache_stats["repartition_runs"] > runs0
    assert eng.cache_stats["exchange_faults"] == 0


def test_single_group_queries_skip_repartition(sharded_pair):
    _, S, _ = sharded_pair
    eng = _repartition_engine(S, "litemat")
    runs0 = eng.cache_stats["repartition_runs"]
    host0 = REGISTRY.counter("shard/combine_runs", path="host").value
    eng.run(PAPER_QUERIES["Q1"])  # one subject-keyed group: host path
    assert eng.cache_stats["repartition_runs"] == runs0
    assert REGISTRY.counter("shard/combine_runs", path="host").value > host0


def test_device_combine_makes_zero_host_uploads(sharded_pair):
    """The acceptance pin: Q4's cross-group join runs with NO host gather.

    `_host_relation` (the host fold's re-upload of the folded relation)
    meters every upload through `device/transfer_bytes{src=combine_upload}`;
    the repartition combine must leave that counter untouched while the
    host fold provably moves it — same query, same engine, same store.
    """
    _, S, _ = sharded_pair
    eng = _repartition_engine(S, "litemat")
    c = REGISTRY.counter("device/transfer_bytes", src="combine_upload")
    before = c.value
    rows, _ = eng.run(PAPER_QUERIES["Q4"])
    assert rows.shape[0] > 0
    assert c.value == before, "device combine leaked a host re-upload"
    eng.use_repartition_join = False
    try:
        eng.run(PAPER_QUERIES["Q4"])
    finally:
        eng.use_repartition_join = True
    assert c.value > before, "host fold should meter its uploads"


# ---------------------------------------------------------------------------
# skewed join keys: one shard owns ~90% of the exchanged rows
# ---------------------------------------------------------------------------


def _skew_onto():
    # no range axiom on p0: range-entailment would type EVERY hot-object
    # row C2, and the hot key's rewrite-mode self-product (hot x hot) blows
    # past the retry budget on the single-device oracle engine too — the
    # skew belongs in the exchange, not in a quadratic join
    return Ontology(
        concepts=["C0", "C1", "C2"], properties=["p0", "p1"],
        subclass=[("C1", "C0"), ("C2", "C0")], subprop=[("p1", "p0")],
        domain={"p0": ["C1"]}, range_={})


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_skewed_join_key_distribution_parity(seed):
    """90% of join keys hash to ONE bin: padding/overflow retries must
    absorb the hot partition without dropping or duplicating rows."""
    onto = _skew_onto()
    raw = generate_random_abox(onto, n_instances=240, n_type_triples=400,
                               n_prop_triples=500, seed=seed)
    rng = np.random.default_rng(seed)
    p0 = fingerprint_string("p0")
    idx = np.where(raw.p == p0)[0]
    assert idx.size > 50
    hot = raw.o[idx[0]]
    n_hot = int(idx.size * 0.9)
    raw.o[rng.permutation(idx)[:n_hot]] = hot
    # the hot instance needs a C2 type so the skewed keys actually join
    raw.s[idx[1]] = hot
    raw.p[idx[1]] = fingerprint_string(RDF_TYPE)
    raw.o[idx[1]] = fingerprint_string("C2")

    K = KnowledgeBase.build(raw)
    S = ShardedKB.build(raw, n_shards=4)
    q = [Pattern("?x", "p0", "?y"), Pattern("?y", "rdf:type", "C2")]
    sel = _sel(q)
    for mode in MODES:
        eng = _repartition_engine(S, mode)
        runs0 = eng.cache_stats["repartition_runs"]
        want, _ = K.query(q, select=sel, mode=mode)
        got, _ = eng.run(q, select=sel)
        assert want.shape[0] > 50, "skewed join should be dense"
        assert np.array_equal(np.asarray(got), want), (seed, mode)
        assert eng.cache_stats["repartition_runs"] > runs0
        assert eng.cache_stats["exchange_faults"] == 0
    assert_partitioned(S)


# ---------------------------------------------------------------------------
# randomized update oracle: mutations keep the device combine bit-identical
# ---------------------------------------------------------------------------


def test_randomized_updates_keep_repartition_parity():
    onto = _skew_onto()
    raw = generate_random_abox(onto, n_instances=200, n_type_triples=300,
                               n_prop_triples=300, seed=5)
    rng = np.random.default_rng(5)
    K = KnowledgeBase.build(raw)
    S = ShardedKB.build(raw, n_shards=4)
    q = [Pattern("?x", "p0", "?y"), Pattern("?y", "rdf:type", "C2")]
    sel = _sel(q)
    for step in range(3):
        op = rng.choice(["insert", "delete", "compact"], p=[0.5, 0.35, 0.15])
        if op == "insert":
            extra = generate_random_abox(
                onto, n_instances=60, n_type_triples=80, n_prop_triples=80,
                seed=100 + step, instance_offset=50_000 * (step + 1))
            K.insert(extra, auto_compact=False)
            S.insert(extra, auto_compact=False)
        elif op == "delete":
            pick = rng.choice(raw.s.shape[0], 30, replace=False)
            batch = (raw.s[pick], raw.p[pick], raw.o[pick])
            K.delete(batch, auto_compact=False)
            S.delete(batch, auto_compact=False)
        else:
            K.compact()
            S.compact()
        mode = MODES[step % 3]
        eng = _repartition_engine(S, mode)
        want, _ = K.query(q, select=sel, mode=mode)
        got, _ = eng.run(q, select=sel)
        assert np.array_equal(np.asarray(got), want), (step, op, mode)
    assert_partitioned(S)


# ---------------------------------------------------------------------------
# exchange faults: degrade to the host fold, never to wrong answers
# ---------------------------------------------------------------------------


def test_exchange_fault_degrades_to_host_fold(sharded_pair):
    K, S, _ = sharded_pair
    eng = _repartition_engine(S, "litemat")
    want = K.answers(PAPER_QUERIES["Q4"], mode="litemat")
    fb0 = REGISTRY.counter("shard/combine_runs", path="host_fallback").value
    with faults.inject() as inj:
        inj.arm("shard.exchange", exc=FaultError, times=1)
        rows, _ = eng.run(PAPER_QUERIES["Q4"])
        assert inj.fired("shard.exchange") == 1
    assert {tuple(r) for r in np.asarray(rows).tolist()} == want
    assert eng.cache_stats["exchange_faults"] == 1
    assert REGISTRY.counter(
        "shard/combine_runs", path="host_fallback").value == fb0 + 1
    # fault exhausted: the next run goes back through the device combine
    runs0 = eng.cache_stats["repartition_runs"]
    rows2, _ = eng.run(PAPER_QUERIES["Q4"])
    assert {tuple(r) for r in np.asarray(rows2).tolist()} == want
    assert eng.cache_stats["repartition_runs"] == runs0 + 1


def test_exchange_hard_crash_propagates(sharded_pair):
    _, S, _ = sharded_pair
    eng = _repartition_engine(S, "litemat")
    with faults.inject() as inj:
        inj.arm("shard.exchange", exc=FaultCrash, times=1)
        with pytest.raises(FaultCrash):
            eng.run(PAPER_QUERIES["Q4"])


# ---------------------------------------------------------------------------
# pin_version after retire: the cursor-continuation regression
# ---------------------------------------------------------------------------


def _tiny_kb():
    onto = _skew_onto()
    raw = generate_random_abox(onto, n_instances=80, n_type_triples=120,
                               n_prop_triples=120, seed=9)
    return KnowledgeBase.build(raw), onto


def test_pin_version_after_retire_degrades_not_errors():
    K, onto = _tiny_kb()
    reg = SnapshotRegistry(K, modes=("litemat",))
    with reg.pin() as pin:
        v0 = pin.version
    extra = generate_random_abox(onto, n_instances=20, n_type_triples=30,
                                 n_prop_triples=30, seed=77,
                                 instance_offset=900_000)
    K.insert(extra, auto_compact=False)
    reg.publish()      # store moved on: v0 is unreferenced and unpublished
    reg.retire()
    assert v0 not in reg.live_versions()
    assert reg.pin_version(v0) is None  # cursor miss -> caller re-pins fresh
    with reg.pin() as fresh:
        assert fresh.version == K.version != v0
        # the degraded cursor is exact at ITS version, just not at v0's
        assert fresh.query([Pattern("?x", "rdf:type", "C0")])[0].shape[0] > 0


def test_pin_version_racing_retire_never_reads_a_dropped_snapshot():
    """A cursor re-pin landing inside retire's victim window must either
    keep the snapshot alive (refs bumped before deletion re-check) or miss
    cleanly — never hand back a Pin onto a deleted snapshot."""
    K, onto = _tiny_kb()
    reg = SnapshotRegistry(K, modes=("litemat",))
    with reg.pin() as pin:
        v0 = pin.version
    extra = generate_random_abox(onto, n_instances=20, n_type_triples=30,
                                 n_prop_triples=30, seed=78,
                                 instance_offset=800_000)
    K.insert(extra, auto_compact=False)
    reg.publish()
    got = {}

    def cursor():
        got["pin"] = reg.pin_version(v0)

    with faults.inject() as inj:
        inj.arm("snapshot.retire", exc=None, delay_s=0.05, times=-1)
        t = threading.Thread(target=cursor)
        # retire picks v0 as a victim, then stalls in the fault window
        # while the cursor races in
        r = threading.Thread(target=reg.retire)
        r.start()
        t.start()
        r.join()
        t.join()
    pin = got["pin"]
    if pin is None:  # the race lost: clean miss, store state intact
        assert v0 not in reg.live_versions()
    else:  # the race won: the snapshot MUST have survived retirement
        assert v0 in reg.live_versions()
        assert pin.version == v0 and pin.stale
        rows, _ = pin.query([Pattern("?x", "rdf:type", "C0")])
        assert rows.shape[0] > 0
        pin.release()
        reg.retire()
        assert v0 not in reg.live_versions()


# ---------------------------------------------------------------------------
# empty-table probes: the lazily-derived ingest store regression
# ---------------------------------------------------------------------------


def test_pair_search_empty_table_returns_zeros():
    """INL probes against a 0-row source (an ingested store keeps ALL rows
    in the delta log, base n=0) must yield empty ranges, not a gather
    from an empty table."""
    empty = jnp.zeros((0,), jnp.int32)
    q = jnp.asarray(np.array([3, 7, 11], np.int32))
    got = np.asarray(ops.pair_search(empty, empty, q, q))
    assert np.array_equal(got, np.zeros(3, np.int32))
    got_hi = np.asarray(ops.pair_search(empty, empty, q, q + 1))
    assert np.array_equal(got_hi, np.zeros(3, np.int32))


def test_ingested_store_survives_inl_plans():
    """Q4 on an ingested LUBM store (empty base, everything in the rewrite
    delta) used to crash in the resident pair-search kernel."""
    from repro.rdf.generator import generate_lubm
    from repro.utils import pair64

    raw = generate_lubm(1, seed=11)
    n = raw.s.shape[0]
    half = n // 2
    parts = [(raw.s[:half], raw.p[:half], raw.o[:half]),
             (raw.s[half:], raw.p[half:], raw.o[half:])]
    S = ShardedKB.ingest(iter(parts), onto=raw.onto, n_shards=2)
    assert S.shards[0].kb.n == 0  # the shape that broke: all rows in delta
    K = KnowledgeBase.build(raw)

    def answers_fp(kb, pats, mode):
        rows, _ = kb.query(pats, mode=mode)
        if rows.size == 0:
            return set()
        ids = jnp.asarray(np.asarray(rows).reshape(-1).astype(np.int32))
        hi, lo, hit = kb.kb.table.extract_fp(ids)
        fps = pair64.combine_np(np.asarray(hi), np.asarray(lo))
        fps = np.where(np.asarray(hit), fps, np.asarray(rows).reshape(-1))
        return {tuple(r) for r in fps.reshape(rows.shape).tolist()}

    for mode in ("litemat", "rewrite"):
        a = answers_fp(S, PAPER_QUERIES["Q4"], mode)
        b = answers_fp(K, PAPER_QUERIES["Q4"], mode)
        assert a == b and len(a) > 0, mode
