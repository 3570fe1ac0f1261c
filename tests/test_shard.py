"""Sharded stores: ShardedKB must be indistinguishable from KnowledgeBase.

The partition invariants under test:

  * results — Q1–Q4 in all three modes, and every query through randomized
    insert/delete/compact sequences, are BIT-IDENTICAL between the
    subject-hash partitioned store and the single-device store (same
    ``select`` ⇒ same global distinct order);
  * placement — every live row of every store (raw and derived) sits on
    its subject's shard after any mutation sequence (range-derived type
    rows migrate through the exchange);
  * laziness — per-mode derivation stays lazy across shards: serving only
    the lite store never runs the full closure of ingested rows;
  * O(delta)-per-shard warmup — post-mutation device transfer rows per
    shard do not depend on the base size.

The shard_map execution path (one device per shard) is pinned in
tests/test_distributed.py via an 8-forced-device subprocess; everything
here runs the per-shard dispatch loop on the suite's single device with 8
(or 4) logical shards — same code above the executor, bit-identical
results by construction of the combine.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.engine import KnowledgeBase, PAPER_QUERIES
from repro.core.query import Pattern
from repro.core.shard import (
    ShardedKB, assert_partitioned, partition_rows, plan_groups, shard_of,
)
from repro.core.tbox import Ontology
from repro.rdf.generator import generate_random_abox
from repro.utils import pair64

MODES = ("litemat", "full", "rewrite")


def _sel(patterns):
    return tuple(dict.fromkeys(
        v for p in patterns for v in (p.s, p.p, p.o)
        if isinstance(v, str) and v.startswith("?")))


def _answers_fp(K, patterns, mode, select):
    """Answers mapped to fingerprint space (ids differ across encodes)."""
    rows, _ = K.query(patterns, select=select, mode=mode)
    if rows.size == 0:
        return set()
    ids = jnp.asarray(rows.reshape(-1).astype(np.int32))
    hi, lo, hit = K.kb.table.extract_fp(ids)
    fps = pair64.combine_np(np.asarray(hi), np.asarray(lo))
    fps = np.where(np.asarray(hit), fps, rows.reshape(-1))
    return {tuple(r) for r in fps.reshape(rows.shape).tolist()}


@pytest.fixture(scope="module")
def sharded_pair(lubm_kb):
    K, raw = lubm_kb
    return K, ShardedKB.build(raw, n_shards=8), raw


# ---------------------------------------------------------------------------
# static parity + placement
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_paper_queries_bit_identical(sharded_pair, mode):
    K, S, _ = sharded_pair
    for name, pats in PAPER_QUERIES.items():
        sel = _sel(pats)
        want, _ = K.query(pats, select=sel, mode=mode)
        got, _ = S.query(pats, select=sel, mode=mode)
        assert np.array_equal(want, got), (mode, name, want.shape, got.shape)


def test_scan_path_parity(sharded_pair):
    """use_index=False (pure kernel scans) through the sharded combine."""
    K, S, _ = sharded_pair
    pats = PAPER_QUERIES["Q3"]
    sel = _sel(pats)
    want, _ = K.query(pats, select=sel, mode="litemat", use_index=False)
    got, _ = S.query(pats, select=sel, mode="litemat", use_index=False)
    assert np.array_equal(want, got)


def test_partition_invariant(sharded_pair):
    _, S, _ = sharded_pair
    assert_partitioned(S)
    # shard sizes should be roughly balanced (hash, not modulo artifacts)
    sizes = np.array([K.kb.n for K in S.shards])
    assert sizes.min() > 0.5 * sizes.mean(), sizes


def test_constant_subject_routes_to_owner_shard(sharded_pair):
    K, S, _ = sharded_pair
    s_id = int(np.asarray(K.kb.spo[0, 0]))
    pats = [Pattern(s_id, "?p", "?y")]
    want, _ = K.query(pats, select=("?p", "?y"))
    got, _ = S.query(pats, select=("?p", "?y"))
    assert np.array_equal(want, got)
    eng = S.engine("litemat")
    routed = eng._route_shards(pats)
    assert routed == [int(shard_of(np.asarray([s_id]), S.n_shards)[0])]


def test_group_planner_locality_rules():
    class _T:  # stand-in tbox: only rdf_type_id is consulted
        rdf_type_id = 7

    q4 = [Pattern("?x", "rdf:type", "Chair"),
          Pattern("?y", "rdf:type", "Department"),
          Pattern("?x", "worksFor", "?y")]
    groups = {frozenset(g) for g in plan_groups(q4, "litemat", _T)}
    assert groups == {frozenset({0, 2}), frozenset({1})}
    # rewrite-mode type patterns bind ?x from BOTH endpoints: never co-hashed
    q3 = [Pattern("?x", "rdf:type", "Professor"),
          Pattern("?x", "memberOf", "?y")]
    assert {frozenset(g) for g in plan_groups(q3, "litemat", _T)} == {
        frozenset({0, 1})}
    assert {frozenset(g) for g in plan_groups(q3, "rewrite", _T)} == {
        frozenset({0}), frozenset({1})}


def test_partition_rows_covers_and_hashes():
    rows = np.stack([np.arange(1000, dtype=np.int32)] * 3, axis=1)
    parts = partition_rows(rows, 8)
    assert sum(p.shape[0] for p in parts) == 1000
    for i, p in enumerate(parts):
        assert (shard_of(p[:, 0], 8) == i).all()


# ---------------------------------------------------------------------------
# randomized update sequences
# ---------------------------------------------------------------------------


def _dag_onto(seed: int) -> Ontology:
    rng = np.random.default_rng(seed)
    nc, npr = int(rng.integers(5, 10)), int(rng.integers(3, 5))
    concepts = [f"C{i}" for i in range(nc)]
    props = [f"p{i}" for i in range(npr)]
    subclass = [(concepts[i], concepts[int(rng.integers(0, i))])
                for i in range(1, nc)]
    if nc > 4:
        subclass.append((concepts[nc - 1], concepts[1]))
    subprop = [(props[i], props[int(rng.integers(0, i))])
               for i in range(1, npr)]
    domain = {props[0]: [concepts[1]]}
    range_ = {props[-1]: [concepts[2]]}  # range axioms exercise the exchange
    return Ontology(concepts=concepts, properties=props, subclass=subclass,
                    subprop=subprop, domain=domain, range_=range_)


@pytest.mark.parametrize("seed", [0, 1])
def test_randomized_update_parity(seed):
    """insert/delete/compact sequences stay bit-identical to the
    single-device store — each step checks one rotating mode, the final
    step all three — and the subject-hash placement survives every step."""
    rng = np.random.default_rng(seed)
    onto = _dag_onto(seed)
    raw = generate_random_abox(onto, n_instances=300, n_type_triples=450,
                               n_prop_triples=400, seed=seed)
    K = KnowledgeBase.build(raw)
    S = ShardedKB.build(raw, n_shards=4)
    queries = [
        [Pattern("?x", "rdf:type", onto.concepts[0])],
        [Pattern("?x", onto.properties[0], "?y")],
        [Pattern("?x", "rdf:type", onto.concepts[1]),
         Pattern("?x", onto.properties[0], "?y")],
        [Pattern("?x", "rdf:type", onto.concepts[0]),
         Pattern("?y", "rdf:type", onto.concepts[2]),
         Pattern("?x", onto.properties[-1], "?y")],
    ]
    n_steps = 3
    for step in range(n_steps):
        op = rng.choice(["insert", "delete", "compact"], p=[0.5, 0.35, 0.15])
        if op == "insert":
            extra = generate_random_abox(
                onto, n_instances=int(rng.integers(50, 200)),
                n_type_triples=int(rng.integers(50, 250)),
                n_prop_triples=int(rng.integers(50, 200)),
                seed=1000 + step, instance_offset=100_000 * (step + 1))
            K.insert(extra, auto_compact=False)
            S.insert(extra, auto_compact=False)
        elif op == "delete":
            n = int(rng.integers(1, 50))
            idx = rng.choice(raw.s.shape[0], n, replace=False)
            batch = (raw.s[idx], raw.p[idx], raw.o[idx])
            K.delete(batch, auto_compact=False)
            S.delete(batch, auto_compact=False)
        else:
            K.compact()
            S.compact()
        modes = MODES if step == n_steps - 1 else (MODES[step % 3],)
        for q in queries:
            sel = _sel(q)
            for mode in modes:
                want, _ = K.query(q, select=sel, mode=mode)
                got, _ = S.query(q, select=sel, mode=mode)
                assert np.array_equal(want, got), (seed, step, op, mode, q)
    assert_partitioned(S)


# ---------------------------------------------------------------------------
# bulk ingest
# ---------------------------------------------------------------------------


def test_ingest_matches_build():
    """Part-streamed ingest == one-shot build, in fingerprint space (the
    two encodes rank instance ids differently)."""
    onto = _dag_onto(3)
    parts = [generate_random_abox(onto, n_instances=150, n_type_triples=250,
                                  n_prop_triples=200, seed=10 + i,
                                  instance_offset=50_000 * i)
             for i in range(4)]
    whole = type(parts[0])(
        s=np.concatenate([p.s for p in parts]),
        p=np.concatenate([p.p for p in parts]),
        o=np.concatenate([p.o for p in parts]),
        onto=onto)
    K = KnowledgeBase.build(whole)
    S = ShardedKB.ingest(parts, n_shards=4)
    assert_partitioned(S)
    queries = [
        [Pattern("?x", "rdf:type", onto.concepts[0])],
        [Pattern("?x", "rdf:type", onto.concepts[1]),
         Pattern("?x", onto.properties[0], "?y")],
    ]
    for q in queries:
        sel = _sel(q)
        for mode in MODES:
            assert _answers_fp(K, q, mode, sel) == _answers_fp(
                S, q, mode, sel), (mode, q)


def test_ingest_lazy_per_mode():
    """Lite-only service of an ingested store never runs the full closure."""
    onto = _dag_onto(4)
    parts = [generate_random_abox(onto, n_instances=100, n_type_triples=150,
                                  n_prop_triples=150, seed=20 + i,
                                  instance_offset=50_000 * i)
             for i in range(3)]
    S = ShardedKB.ingest(parts, n_shards=4)
    assert S.mat_counts == {"litemat": 0, "full": 0}
    S.query([Pattern("?x", "rdf:type", onto.concepts[0])], mode="litemat")
    assert S.mat_counts["litemat"] == len(parts)
    assert S.mat_counts["full"] == 0


# ---------------------------------------------------------------------------
# O(delta) per-shard warmup
# ---------------------------------------------------------------------------


def test_shard_warmup_transfers_independent_of_base_size():
    """Every shard's post-insert device refresh ships EXACTLY the rows its
    own delta log predicts (one pow2 bucket per warmed key), at 1x AND 4x
    base — the per-shard O(delta) pin.  (The raw per-shard numbers cannot
    be compared across scales directly: the dictionary ranks the delta's
    new instance ids differently over different bases, so the hash
    partition of the same delta differs — what must NOT differ is the
    transfer/delta-size relation, which an O(base) leak would break.)"""
    from repro.core.index import pow2_bucket

    onto = _dag_onto(5)
    for scale in (1, 4):
        raw = generate_random_abox(
            onto, n_instances=800 * scale, n_type_triples=1500 * scale,
            n_prop_triples=1200 * scale, seed=6)
        S = ShardedKB.build(raw, n_shards=4)
        S.prewarm([[Pattern("?x", "rdf:type", onto.concepts[0])]],
                  modes=("litemat",))
        S.warm_device("litemat", keys=("pos",))
        before = [K.dev_cache("litemat").stats["upload_delta_rows"]
                  for K in S.shards]
        delta = generate_random_abox(
            onto, n_instances=64, n_type_triples=128, n_prop_triples=128,
            seed=99, instance_offset=10_000_000)
        S.insert(delta, auto_compact=False)
        S.warm_device("litemat", keys=("pos",))
        got = [K.dev_cache("litemat").stats["upload_delta_rows"] - b
               for K, b in zip(S.shards, before)]
        want = [pow2_bucket(K.delta.log("litemat").n)
                if K.delta.log("litemat").n else 0 for K in S.shards]
        assert got == want, (scale, got, want)


# ---------------------------------------------------------------------------
# sharded serving
# ---------------------------------------------------------------------------


def test_sharded_serving_matches_single(sharded_pair):
    from repro.serving.engine import QueryServer, ShardedQueryServer

    K, S, raw = sharded_pair
    names = ["Professor", "Student", "Chair", "Course"]
    qs = QueryServer(K, topk=16)
    qss = ShardedQueryServer(S, topk=16)
    c1, m1 = qs.class_members(names)
    c2, m2 = qss.class_members(names)
    assert np.array_equal(c1, c2)
    assert np.array_equal(m1, m2)
    cp1, s1 = qs.class_prop_join(["Professor", "Chair"],
                                 ["worksFor", "memberOf"])
    cp2, s2 = qss.class_prop_join(["Professor", "Chair"],
                                  ["worksFor", "memberOf"])
    assert np.array_equal(cp1, cp2)
    assert np.array_equal(s1, s2)


def test_windowed_inl_probe_parity(sharded_pair):
    """The INL join's pair-search probes must answer exactly what
    evaluate-then-join answers: Q4 with INL on equals Q4 with INL off."""
    from repro.core import query as qmod

    K, _, _ = sharded_pair
    pats = PAPER_QUERIES["Q4"]
    sel = _sel(pats)
    eng = qmod.QueryEngine(kb=K.kb, spo=K.lite_spo, mode="litemat",
                           dtb=K.dtb)
    want, _ = eng.run(pats, select=sel)
    eng.use_inl = False
    got, _ = eng.run(pats, select=sel)
    assert np.array_equal(want, got)
    assert want.shape[0] > 0
