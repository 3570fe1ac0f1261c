"""The plain reference's RDFS entailment, on triples written by hand."""
import numpy as np

from chipbench import lubm
from chipbench.reference import Reference


def _ref(rows, rules=None):
    onto = lubm.ontology()
    f = lubm.fingerprint
    s, p, o = (np.asarray([f(x) if isinstance(x, str) else x for x in col],
                          np.int64) for col in zip(*rows))
    triples = lubm.Triples(s=s, p=p, o=o)
    return Reference(triples, onto) if rules is None else \
        Reference(triples, onto, rules)


ALICE, BOB, DEPT, UNIV = 11, 12, 21, 31


def test_entailment_rules():
    r = _ref([(ALICE, "headOf", DEPT), (BOB, "rdf:type", "GraduateStudent"),
              (BOB, "memberOf", DEPT), (DEPT, "subOrganizationOf", UNIV)])
    chairs = r.answers([("?x", "rdf:type", "Chair")], ["?x"])
    assert chairs.tolist() == [[ALICE]]  # domain(headOf)
    people = r.answers([("?x", "rdf:type", "Person")], ["?x"])
    assert sorted(people.ravel().tolist()) == [ALICE, BOB]  # subclass
    members = r.answers([("?x", "memberOf", DEPT)], ["?x"])
    assert sorted(members.ravel().tolist()) == [ALICE, BOB]  # subproperty
    orgs = r.answers([("?x", "rdf:type", "Organization")], ["?x"])
    assert sorted(orgs.ravel().tolist()) == [DEPT, UNIV]  # range


def test_joins_and_projection():
    r = _ref([(ALICE, "worksFor", DEPT), (BOB, "memberOf", DEPT),
              (DEPT, "subOrganizationOf", UNIV), (BOB, "rdf:type", "Student")])
    got = r.answers([("?x", "rdf:type", "Student"), ("?x", "memberOf", "?y"),
                     ("?y", "subOrganizationOf", UNIV)], ["?x", "?y"])
    assert got.tolist() == [[BOB, DEPT]]


def test_control_drops_domain_and_range():
    rows = [(ALICE, "headOf", DEPT)]
    ctl = _ref(rows, ("subclass", "subprop"))
    assert ctl.answers([("?x", "rdf:type", "Chair")], ["?x"]).size == 0
    assert ctl.answers([("?x", "memberOf", DEPT)], ["?x"]).tolist() == [[ALICE]]
