"""LUBM-shaped data for the benchmark: the Univ-Bench ontology and an ABox.

The benchmark owns its input data so that no change to the system under
test can change what it is measured on.  Terms are 61-bit fingerprints:
class and property names hash their name (blake2b), entities hash a
small-integer tuple (splitmix64), the same encoding the store's own
generator and N-Triples loader use, so the store takes these columns as
they are.  Per university: 15-25 departments, each with its research
groups, four faculty ranks, courses, publications, undergraduate and
graduate students, advisors, teaching assistants and literals; about
110K triples per university.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass
from functools import lru_cache
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
_MASK61 = (1 << 61) - 1
_MASK64 = (1 << 64) - 1

# entity kinds: the first element of an entity's fingerprint tuple
(K_UNIV, K_DEPT, K_RG, K_FP, K_AP, K_ASP, K_LECT, K_UG, K_GR, K_CRS, K_GCRS,
 K_PUB, K_RES) = range(1, 14)
K_LIT = 20
FACULTY_CLASS = {K_FP: "FullProfessor", K_AP: "AssociateProfessor",
                 K_ASP: "AssistantProfessor", K_LECT: "Lecturer"}


def fingerprint(name: str) -> int:
    """Fingerprint of a class or property name."""
    h = hashlib.blake2b(name.encode("utf-8"), digest_size=8).digest()
    return int.from_bytes(h, "little") & _MASK61


def _splitmix64(x):
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        z = (x + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(_MASK64)
        z = ((z ^ (z >> np.uint64(30)))
             * np.uint64(0xBF58476D1CE4E5B9)) & np.uint64(_MASK64)
        z = ((z ^ (z >> np.uint64(27)))
             * np.uint64(0x94D049BB133111EB)) & np.uint64(_MASK64)
        return z ^ (z >> np.uint64(31))


def entity(*parts) -> np.ndarray:
    """Fingerprint of an entity named by a tuple of small integers."""
    acc = np.uint64(0x243F6A8885A308D3)
    for p in parts:
        with np.errstate(over="ignore"):
            acc = _splitmix64(acc ^ _splitmix64(np.asarray(p, np.uint64)))
    return (acc & np.uint64(_MASK61)).astype(np.int64)


@lru_cache(maxsize=None)
def ontology(name: str = "univ-bench") -> dict:
    """The ontology as data: concepts, properties, subclass and
    subproperty edges, domain and range axioms."""
    return json.loads((HERE / "ontologies" / f"{name}.json").read_text())


@dataclass
class Triples:
    s: np.ndarray  # int64 fingerprints
    p: np.ndarray
    o: np.ndarray

    @property
    def n(self) -> int:
        return int(self.s.shape[0])


def generate(n_universities: int, seed: int, onto: dict) -> Triples:
    """The explicit triples of LUBM(``n_universities``, ``seed``)."""
    rng = np.random.default_rng(seed)
    cols = ([], [], [])

    def add(s, p, o):
        s, p, o = np.broadcast_arrays(np.asarray(s, np.int64),
                                      np.asarray(p, np.int64),
                                      np.asarray(o, np.int64))
        for c, v in zip(cols, (s, p, o)):
            c.append(v.ravel())

    cfp = {c: fingerprint(c) for c in onto["concepts"]}
    pfp = {p: fingerprint(p) for p in onto["properties"]}
    TYPE = fingerprint(onto["rdf_type"])

    def lit(field, owner):
        return entity(K_LIT, field, np.asarray(owner, np.int64))

    univs = entity(K_UNIV, np.arange(n_universities), 0, 0)
    add(univs, TYPE, cfp["University"])
    for u in range(n_universities):
        for d in range(int(rng.integers(15, 26))):
            dept = entity(K_DEPT, u, d, 0)
            add(dept, TYPE, cfp["Department"])
            add(dept, pfp["subOrganizationOf"], univs[u])
            n_rg = int(rng.integers(10, 21))
            rgs = entity(K_RG, u, d, np.arange(n_rg))
            add(rgs, TYPE, cfp["ResearchGroup"])
            add(rgs, pfp["subOrganizationOf"], dept)
            res = entity(K_RES, u, d, np.arange(n_rg))
            add(res, TYPE, cfp["Research"])
            add(rgs, pfp["researchProject"], res)

            counts = {K_FP: int(rng.integers(7, 11)),
                      K_AP: int(rng.integers(10, 15)),
                      K_ASP: int(rng.integers(8, 12)),
                      K_LECT: int(rng.integers(5, 8))}
            fac, prof = [], []
            for kind, cnt in counts.items():
                f = entity(kind, u, d, np.arange(cnt))
                fac.append(f)
                if kind != K_LECT:
                    prof.append(f)
                add(f, TYPE, cfp[FACULTY_CLASS[kind]])
            faculty, professors = np.concatenate(fac), np.concatenate(prof)
            nf = faculty.shape[0]
            add(faculty, pfp["worksFor"], dept)
            add(faculty[:1], pfp["headOf"], dept)  # the chair: no Chair type
            for prop in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                         "doctoralDegreeFrom"):
                add(faculty, pfp[prop],
                    univs[rng.integers(0, n_universities, nf)])

            n_crs, n_gcrs = nf * 2, max(nf, 1)
            courses = entity(K_CRS, u, d, np.arange(n_crs))
            gcourses = entity(K_GCRS, u, d, np.arange(n_gcrs))
            add(courses, TYPE, cfp["Course"])
            add(gcourses, TYPE, cfp["GraduateCourse"])
            add(faculty, pfp["teacherOf"], courses[rng.permutation(n_crs)[:nf]])
            add(faculty, pfp["teacherOf"], gcourses[rng.integers(0, n_gcrs, nf)])

            pubs_per = rng.integers(5, 16, nf)
            pubs = entity(K_PUB, u, d, np.arange(int(pubs_per.sum())))
            kinds = [cfp[c] for c in ("JournalArticle", "ConferencePaper",
                                      "TechnicalReport", "Book")]
            add(pubs, TYPE, rng.choice(kinds, size=pubs.shape[0]))
            add(pubs, pfp["publicationAuthor"], np.repeat(faculty, pubs_per))

            n_ug = nf * int(rng.integers(8, 15))
            n_gr = nf * int(rng.integers(3, 5))
            ug = entity(K_UG, u, d, np.arange(n_ug))
            gr = entity(K_GR, u, d, np.arange(n_gr))
            add(ug, TYPE, cfp["UndergraduateStudent"])
            add(gr, TYPE, cfp["GraduateStudent"])
            add(ug, pfp["memberOf"], dept)
            add(gr, pfp["memberOf"], dept)
            for _ in range(3):
                add(ug, pfp["takesCourse"], courses[rng.integers(0, n_crs, n_ug)])
            for _ in range(2):
                add(gr, pfp["takesCourse"], gcourses[rng.integers(0, n_gcrs, n_gr)])
            add(gr, pfp["advisor"],
                professors[rng.integers(0, professors.shape[0], n_gr)])
            ug_adv = ug[rng.random(n_ug) < 0.2]
            add(ug_adv, pfp["advisor"],
                professors[rng.integers(0, professors.shape[0], ug_adv.shape[0])])
            add(gr, pfp["undergraduateDegreeFrom"],
                univs[rng.integers(0, n_universities, n_gr)])
            tas = gr[rng.random(n_gr) < 0.2]
            add(tas, pfp["teachingAssistantOf"],
                courses[rng.integers(0, n_crs, tas.shape[0])])

            people = np.concatenate([faculty, ug, gr])
            for field, prop in ((1, "emailAddress"), (2, "name"),
                                (3, "telephone")):
                add(people, pfp[prop], lit(field, people))
            add(faculty, pfp["researchInterest"], lit(4, faculty))
    s, p, o = (np.concatenate(c) for c in cols)
    return Triples(s=s, p=p, o=o)
