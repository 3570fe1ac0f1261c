"""Plain reference: RDFS entailment and conjunctive queries in numpy.

Independent of the system under test: it reads the benchmark's own
triples and ontology (chipbench/lubm.py) and nothing the store built.

Entailment, over fingerprints:
  * rdfs5/7: a triple ``(s, p, o)`` entails ``(s, q, o)`` for every
    super-property ``q`` of ``p``;
  * rdfs2/3: it types ``s`` with the domain and ``o`` with the range of
    ``p`` and of every super-property of ``p``;
  * rdfs9/11: every explicit or entailed type also holds for each
    super-class.

A query is a list of ``(s, p, o)`` patterns over variables (``?x``),
names from the ontology and fingerprints (ints).  Its answer is the
sorted array of distinct ``select``-projected fingerprint tuples.

``rules`` drops rules for a control: ``("subclass", "subprop")`` leaves
out domain and range entailment, the guarantee a store that skipped it
would break.
"""
from __future__ import annotations

import numpy as np

from chipbench.lubm import Triples, fingerprint

ALL_RULES = ("subclass", "subprop", "domain_range")


def _ancestors(edges, nodes) -> dict:
    """name -> reflexive-transitive set of supers along (sub, sup) edges."""
    up = {}
    for sub, sup in edges:
        up.setdefault(sub, set()).add(sup)
    out = {}
    for n in nodes:
        seen, stack = {n}, [n]
        while stack:
            for s in up.get(stack.pop(), ()):
                if s not in seen:
                    seen.add(s)
                    stack.append(s)
        out[n] = seen
    return out


def _expand(keys: np.ndarray, table: dict) -> tuple:
    """For each element of ``keys``: (row index, value) for every value in
    ``table[key]``; keys not in the table yield nothing."""
    idx, val = [], []
    for k, vs in table.items():
        rows = np.flatnonzero(keys == k)
        if rows.size == 0 or not vs:
            continue
        for v in vs:
            idx.append(rows)
            val.append(np.full(rows.size, v, np.int64))
    if not idx:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(idx), np.concatenate(val)


class Reference:
    """The RDFS closure of a triple set, with query evaluation."""

    def __init__(self, triples: Triples, onto: dict, rules=ALL_RULES):
        self.type_fp = fingerprint(onto["rdf_type"])
        self.cfp = {c: fingerprint(c) for c in onto["concepts"]}
        self.pfp = {p: fingerprint(p) for p in onto["properties"]}
        c_anc = _ancestors(onto["subclass"], onto["concepts"])
        p_anc = _ancestors(onto["subprop"], onto["properties"])
        if "subclass" not in rules:
            c_anc = {c: {c} for c in c_anc}
        sup_c = {self.cfp[c]: [self.cfp[a] for a in sorted(v)]
                 for c, v in c_anc.items()}
        sup_p = {self.pfp[p]: [self.pfp[a] for a in sorted(v)]
                 for p, v in p_anc.items()}
        dom, rng = {}, {}
        for p, anc in p_anc.items():
            d = {c for a in anc for c in onto["domain"].get(a, ())}
            r = {c for a in anc for c in onto["range"].get(a, ())}
            dom[self.pfp[p]] = sorted({x for c in d for x in sup_c[self.cfp[c]]})
            rng[self.pfp[p]] = sorted({x for c in r for x in sup_c[self.cfp[c]]})

        s, p, o = triples.s, triples.p, triples.o
        is_type = p == self.type_fp
        S, P, O = [], [], []
        # explicit types and their super-classes
        i, c = _expand(o[is_type], sup_c)
        ts = s[is_type]
        S += [ts, ts[i]]
        P += [np.full(ts.size + i.size, self.type_fp, np.int64)]
        O += [o[is_type], c]
        # other triples, lifted to every super-property
        ns, np_, no = s[~is_type], p[~is_type], o[~is_type]
        if "subprop" in rules:
            i, q = _expand(np_, sup_p)
            S.append(ns[i]), P.append(q), O.append(no[i])
        else:
            S.append(ns), P.append(np_), O.append(no)
        if "domain_range" in rules:
            i, c = _expand(np_, dom)
            S.append(ns[i]), P.append(np.full(i.size, self.type_fp)), O.append(c)
            i, c = _expand(np_, rng)
            S.append(no[i]), P.append(np.full(i.size, self.type_fp)), O.append(c)
        S, P, O = (np.concatenate(c) for c in (S, P, O))
        order = np.lexsort((O, S, P))  # by predicate, then subject, object
        rows = np.stack([S[order], P[order], O[order]], axis=1)
        new = np.ones(len(rows), bool)
        new[1:] = (rows[1:] != rows[:-1]).any(axis=1)
        self.rows = rows[new]  # the closure, distinct, grouped by predicate
        self._p = self.rows[:, 1]

    @property
    def n(self) -> int:
        return int(self.rows.shape[0])

    def _const(self, term, pos: str) -> int:
        if isinstance(term, (int, np.integer)):
            return int(term)
        if pos == "p" and term == "rdf:type":
            return self.type_fp
        table = self.pfp if pos == "p" else self.cfp
        if term not in table:
            raise KeyError(f"unknown term {term!r}")
        return table[term]

    def _match(self, pat) -> dict:
        """One pattern -> {var: column} of its bindings."""
        spec = [t if isinstance(t, str) and t.startswith("?")
                else self._const(t, pos) for t, pos in zip(pat, "spo")]
        rows = self.rows
        if not isinstance(spec[1], str):
            lo, hi = np.searchsorted(self._p, [spec[1], spec[1] + 1])
            rows = rows[lo:hi]
        keep = np.ones(rows.shape[0], bool)
        cols = {}
        for j, t in enumerate(spec):
            if isinstance(t, str):
                if t in cols:  # a variable repeated in one pattern
                    keep &= rows[:, j] == rows[:, cols[t]]
                else:
                    cols[t] = j
            else:
                keep &= rows[:, j] == t
        rows = rows[keep]
        return {v: rows[:, j] for v, j in cols.items()}

    def answers(self, patterns, select) -> np.ndarray:
        """Conjunctive query -> sorted unique int64 array of the
        select-projected fingerprint tuples.  Joins go smallest first,
        each next pattern connected where one is, filtered to the values
        its shared variables already have."""
        tables = [self._match(p) for p in patterns]
        size = lambda i: len(next(iter(tables[i].values())))  # noqa: E731
        left = sorted(range(len(tables)), key=size)
        rel = tables[left.pop(0)]
        while left:
            linked = [i for i in left if set(tables[i]) & set(rel)]
            i = (linked or left)[0]
            left.remove(i)
            b = tables[i]
            for v in set(b) & set(rel):
                keep = np.isin(b[v], rel[v])
                b = {k: c[keep] for k, c in b.items()}
            rel = _join(rel, b)
        rows = np.stack([rel[v] for v in select], axis=1)
        return np.unique(rows, axis=0)


def _join(a: dict, b: dict) -> dict:
    """Natural join of two binding tables (hash-free: sort and search)."""
    shared = [v for v in a if v in b]
    na = len(next(iter(a.values())))
    nb = len(next(iter(b.values())))
    if not shared:
        ia = np.repeat(np.arange(na), nb)
        ib = np.tile(np.arange(nb), na)
    else:
        keys = np.concatenate([np.stack([a[v] for v in shared], 1),
                               np.stack([b[v] for v in shared], 1)])
        _, inv = np.unique(keys, axis=0, return_inverse=True)
        inv = inv.reshape(-1)
        ka, kb = inv[:na], inv[na:]
        order = np.argsort(kb, kind="stable")
        kbs = kb[order]
        lo = np.searchsorted(kbs, ka, "left")
        cnt = np.searchsorted(kbs, ka, "right") - lo
        ia = np.repeat(np.arange(na), cnt)
        start = np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
        ib = order[start + np.arange(ia.size)]
    out = {v: col[ia] for v, col in a.items()}
    out.update({v: col[ib] for v, col in b.items() if v not in out})
    return out
