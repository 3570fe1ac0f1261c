"""Query completeness: lite == full == rewrite (the paper's own check)."""
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.engine import KnowledgeBase, PAPER_QUERIES
from repro.core.query import INVALID, Pattern, QueryEngine, Relation, join, sig_label
from repro.core.tbox import Ontology
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY
from repro.rdf.generator import generate_random_abox

# LUBM Q9: student, faculty advisor, a course the advisor teaches and the
# student takes — a triangle, so its last join shares two variables
Q9 = [Pattern("?X", "rdf:type", "Student"), Pattern("?Y", "rdf:type", "Faculty"),
      Pattern("?Z", "rdf:type", "Course"), Pattern("?X", "advisor", "?Y"),
      Pattern("?Y", "teacherOf", "?Z"), Pattern("?X", "takesCourse", "?Z")]


def test_paper_queries_complete(lubm_kb):
    K, _ = lubm_kb
    for qn, pats in PAPER_QUERIES.items():
        res = {m: K.answers(pats, mode=m) for m in ("litemat", "full", "rewrite")}
        assert res["litemat"] == res["full"] == res["rewrite"], qn
        assert len(res["litemat"]) > 0, f"{qn} should not be empty"


def test_q1_professor_counts(lubm_kb):
    """Q1 must include all Professor subsumees but exclude e.g. Lecturers."""
    K, _ = lubm_kb
    profs = K.answers(PAPER_QUERIES["Q1"])
    full_prof = K.answers([Pattern("?x", "rdf:type", "FullProfessor")])
    lect = K.answers([Pattern("?x", "rdf:type", "Lecturer")])
    assert full_prof <= profs
    assert not (lect & profs)


def test_q4_chair_is_derived_only(lubm_kb):
    """No explicit Chair triples exist; Chair answers come from domain(headOf)
    (lite/full) or the domain-aware rewriting (the paper's Q4' observation)."""
    K, _ = lubm_kb
    raw_engine = K.engine("rewrite")
    chairs = K.answers([Pattern("?x", "rdf:type", "Chair")], mode="litemat")
    assert len(chairs) > 0
    # the raw store has no explicit triple with the Chair id as object
    cid = K.kb.tbox.concept_id("Chair")
    spo = np.asarray(K.kb.spo)
    tmask = spo[:, 1] == K.kb.tbox.rdf_type_id
    assert not (spo[tmask, 2] == cid).any()
    # and the rewrite engine still finds them (via ?x headOf ?y)
    assert K.answers([Pattern("?x", "rdf:type", "Chair")], mode="rewrite") == chairs


def test_property_hierarchy_query(lubm_kb):
    """?x worksFor ?y must be included in ?x memberOf ?y (subproperty)."""
    K, _ = lubm_kb
    member = K.answers([Pattern("?x", "memberOf", "?y")])
    works = K.answers([Pattern("?x", "worksFor", "?y")])
    head = K.answers([Pattern("?x", "headOf", "?y")])
    assert works <= member
    assert head <= works


def test_join_on_object_position(lubm_kb):
    """Object-object / subject-object joins: advisor's department."""
    K, _ = lubm_kb
    pats = [
        Pattern("?s", "advisor", "?prof"),
        Pattern("?prof", "worksFor", "?dept"),
    ]
    res = {m: K.answers(pats, select=("?s", "?dept"), mode=m)
           for m in ("litemat", "full", "rewrite")}
    assert res["litemat"] == res["full"] == res["rewrite"]
    assert len(res["litemat"]) > 100


def test_inl_join_fallback_matches_merge_join(lubm_kb):
    """Q4-style tiny-side joins: INL probe plan == merge-join plan.

    The planner must actually convert Q4's dominant pattern (worksFor,
    ~40x the Chair count) to an index-nested-loop probe of the PSO
    permutation, and the answers must be identical to the merge-join plan
    with INL disabled.
    """
    K, _ = lubm_kb
    for mode in ("litemat", "full"):
        eng = K.engine(mode)
        sigs, *_ = eng._plan(PAPER_QUERIES["Q4"], None)
        assert any(s.strategy == "inl" for s in sigs), mode
        got = K.answers(PAPER_QUERIES["Q4"], mode=mode)
        eng.use_inl = False
        try:
            rows, _ = eng.run(PAPER_QUERIES["Q4"])
        finally:
            eng.use_inl = True
        assert got == {tuple(r) for r in rows.tolist()}
        assert len(got) > 0


def test_inl_join_object_probe(lubm_kb):
    """Constant-object probes take the POS permutation (o is the bound var)."""
    K, _ = lubm_kb
    pats = [Pattern("?x", "rdf:type", "Chair"),
            Pattern("?s", "advisor", "?x")]
    eng = K.engine("litemat")
    sigs, *_ = eng._plan(pats, None)
    inl = [s for s in sigs if s.strategy == "inl"]
    assert inl and inl[0].store == "pos" and inl[0].probe_pos == 2
    got = K.answers(pats, mode="litemat")
    eng.use_inl = False
    try:
        rows, _ = eng.run(pats)
    finally:
        eng.use_inl = True
    assert got == {tuple(r) for r in rows.tolist()}


def test_rewrite_dual_branch_is_one_pass(lubm_kb):
    """(?x rdf:type Person) has dom AND rng branches: ONE dual-mask pass.

    Person entails through domain properties (memberOf, advisor, ...) and
    range properties (member, publicationAuthor) — the dual-branch shape
    the dual-mask compaction kernel resolves in one grid pass per source.
    The trace-time pass counters pin it: >= 1 dual pass, and at most the
    single pass DISTINCT's dedup owns (none per branch); answers stay
    equal to litemat.
    """
    from repro.core.query import QueryEngine
    from repro.kernels import ops

    K, _ = lubm_kb
    q = [Pattern("?x", "rdf:type", "Person")]
    want = K.answers(q, mode="litemat")
    eng = QueryEngine(kb=K.kb, spo=K.kb.spo, mode="rewrite", dtb=K.dtb)
    ops.compact_indices.clear_cache()
    ops.dual_compact_indices.clear_cache()
    ops.reset_pass_counters()
    rows, _ = eng.run(q)
    assert ops.pass_counters["dual_compact"] >= 1, ops.pass_counters
    assert ops.pass_counters["compact"] <= 1, ops.pass_counters
    assert {tuple(r) for r in rows.tolist()} == want
    assert len(want) > 0


@st.composite
def dag_onto(draw):
    nc = draw(st.integers(4, 10))
    concepts = [f"C{i}" for i in range(nc)]
    edges = []
    for i in range(1, nc):
        for p in draw(st.lists(st.integers(0, i - 1), min_size=1, max_size=2,
                               unique=True)):
            edges.append((concepts[i], concepts[p]))
    return Ontology(concepts=concepts, properties=["p0", "p1"], subclass=edges,
                    subprop=[("p1", "p0")], domain={}, range_={}), draw(st.integers(0, 999))


@given(dag_onto())
@settings(max_examples=10, deadline=None)
def test_completeness_on_random_dags(spec):
    """Multiple-inheritance ontologies: spill intervals keep queries complete."""
    onto, seed = spec
    raw = generate_random_abox(onto, n_instances=40, n_type_triples=60,
                               n_prop_triples=30, seed=seed)
    K = KnowledgeBase.build(raw)
    for cname in onto.concepts[: min(len(onto.concepts), 6)]:
        pats = [Pattern("?x", "rdf:type", cname)]
        res = {m: K.answers(pats, mode=m) for m in ("litemat", "full", "rewrite")}
        assert res["litemat"] == res["full"] == res["rewrite"], cname


def _random_relation(rng, vars_, n, n_invalid, domain):
    """Rows drawn from a small domain (so keys repeat); the invalid rows
    carry in-domain values, which must never join."""
    cols = rng.integers(0, domain, size=(len(vars_), n)).astype(np.int32)
    valid = np.ones(n, bool)
    valid[rng.choice(n, n_invalid, replace=False)] = False
    return cols, valid


def _join_reference(a_vars, a_cols, a_valid, b_vars, b_cols, b_valid, on):
    """Nested-loop join rows (a's vars, then b's new vars) and the number
    of row pairs equal on ``on``."""
    rows, n_on = [], 0
    b_new = [j for j, v in enumerate(b_vars) if v not in a_vars]
    for i in np.flatnonzero(a_valid):
        for j in np.flatnonzero(b_valid):
            if all(a_cols[a_vars.index(v), i] == b_cols[b_vars.index(v), j]
                   for v in on):
                n_on += 1
                if all(a_cols[a_vars.index(v), i]
                       == b_cols[b_vars.index(v), j]
                       for v in a_vars if v in b_vars):
                    rows.append(tuple(a_cols[:, i]) + tuple(b_cols[b_new, j]))
    return Counter(rows), n_on


@pytest.mark.parametrize("vmapped", [False, True], ids=["solo", "vmap"])
@pytest.mark.parametrize("n_shared", [1, 2, 3])
def test_join_on_shared_vars_matches_reference(n_shared, vmapped):
    """``join`` with one, two and three shared vars against a nested loop,
    with repeated keys and invalid rows on both sides.  ``overflow`` counts
    the pairs equal on the key vars (the first two shared, or the one):
    with two shared that is the join's true size."""
    rng = np.random.default_rng(n_shared)
    shared = ["?s0", "?s1", "?s2"][:n_shared]
    a_vars = tuple(shared + ["?a"])
    b_vars = tuple(["?b"] + shared[::-1])  # b lists them in another order
    keys = shared[:2]
    batch = 2 if vmapped else 1
    rels = [(*_random_relation(rng, a_vars, 48, 7, 3),
             *_random_relation(rng, b_vars, 40, 5, 3)) for _ in range(batch)]
    refs = [_join_reference(a_vars, ac, av, b_vars, bc, bv, keys)
            for ac, av, bc, bv in rels]
    cap = 1024

    def run(ac, av, bc, bv, cap):
        out = join(Relation(a_vars, ac, av, jnp.int32(0)),
                   Relation(b_vars, bc, bv, jnp.int32(0)), cap)
        return out.cols, out.valid, out.overflow

    stacked = [jnp.asarray(np.stack(x)) for x in zip(*rels)]
    if vmapped:
        cols, valid, overflow = jax.vmap(lambda *x: run(*x, cap))(*stacked)
        small = jax.vmap(lambda *x: run(*x, 16))(*stacked)[2]
    else:
        cols, valid, overflow = (x[None] for x in run(*(x[0] for x in stacked),
                                                      cap))
        small = run(*(x[0] for x in stacked), 16)[2][None]
    for k, (rows_ref, n_on) in enumerate(refs):
        assert n_on <= cap and int(overflow[k]) == 0
        got = np.asarray(cols[k])[:, np.asarray(valid[k])]
        assert Counter(map(tuple, got.T.tolist())) == rows_ref
        assert not (np.asarray(cols[k])[:, ~np.asarray(valid[k])]
                    != int(INVALID)).any()
        assert sum(rows_ref.values()) > 0
        assert int(small[k]) == max(n_on - 16, 0)


def test_q9_triangle_complete(lubm_kb):
    """Q9's triangle: litemat == full == rewrite, and the all-merge plan
    (its last join on two shared vars) == the index-nested-loop plan."""
    K, _ = lubm_kb
    res = {m: K.answers(Q9, mode=m) for m in ("litemat", "full", "rewrite")}
    assert res["litemat"] == res["full"] == res["rewrite"]
    assert len(res["litemat"]) > 0
    for mode in ("litemat", "full"):
        eng = K.engine(mode)
        eng.use_inl = False
        try:
            planned = eng._plan(Q9, None)
            rows, sel = eng._run_planned(planned)
        finally:
            eng.use_inl = True
        assert eng._composite_joins(planned[0]) >= 1
        assert {tuple(r) for r in rows.tolist()} == K.answers(
            Q9, select=sel, mode=mode)


@pytest.mark.parametrize("mode", ["litemat", "full"])
def test_q9_triangle_fits_planned_caps(lubm_kb, mode):
    """With the planner's own caps, the all-merge Q9 plan does not overflow
    on its first execution: the triangle's join counts true matches only.
    The dispatch counts its composite-key join and retries nothing."""
    K, _ = lubm_kb
    eng = K.engine(mode)
    eng.use_inl = False
    try:
        planned = eng._plan(Q9, None)
    finally:
        eng.use_inl = True
    sigs, dyns, caps, join_cap, sel, stores = planned[:6]
    fn = eng._executable(("exec", mode, sigs, tuple(caps), join_cap, sel),
                         sigs, tuple(caps), join_cap, sel)
    assert int(fn(stores, dyns)[2]) == 0
    slabel = sig_label(sigs)
    n = eng._composite_joins(sigs)
    assert n == 1
    before = REGISTRY.counter_value("join/composite_key", site="query",
                                    sig=slabel)
    retries = REGISTRY.counter_value("join/capacity_retry", site="query",
                                     sig=slabel, shard="local")
    tracer = obs_trace.Tracer()
    tr = tracer.new_trace()
    with obs_trace.activate(tracer.start_root(tr, "test.q9")):
        rows, _ = eng._run_planned(planned)
    tracer.finish_trace(tr)
    assert len(rows) > 0
    assert REGISTRY.counter_value("join/composite_key", site="query",
                                  sig=slabel) == before + n
    assert REGISTRY.counter_value("join/capacity_retry", site="query",
                                  sig=slabel, shard="local") == retries
    dispatch = [s for s in tr.to_dict()["spans"]
                if s["name"] == "query.dispatch"]
    assert [s["attrs"]["composite_joins"] for s in dispatch] == [n]


def test_single_shared_var_join_is_not_composite(lubm_kb):
    """Joins on one shared var keep the single-key path: no
    ``join/composite_key`` count, and 0 on the dispatch span."""
    K, _ = lubm_kb
    pats = [Pattern("?s", "advisor", "?prof"),
            Pattern("?prof", "worksFor", "?dept")]
    eng = K.engine("litemat")
    planned = eng._plan(pats, None)
    assert QueryEngine._composite_joins(planned[0]) == 0
    before = REGISTRY.values("join/composite_key")
    rows, _ = eng._run_planned(planned)
    assert len(rows) > 0
    assert REGISTRY.values("join/composite_key") == before
