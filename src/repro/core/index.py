"""Device-resident sorted indexes over an encoded triple store.

LiteMat's encoding turns RDFS inference into interval containment, so a
triple pattern with a constant predicate (and, for rdf:type patterns, a
constant concept interval) selects a *contiguous run* of a suitably sorted
store — the observation behind self-indexed RDF stores (WaterFowl,
k²-Triples).  This module materializes four permutations of the (N, 3)
store, each lazily on first use:

  * POS — rows ordered by (predicate, object, subject): resolves
    ``(?x p ?y)`` and ``(?x rdf:type C)`` patterns,
  * PSO — rows ordered by (predicate, subject, object): resolves
    ``(s p ?y)`` patterns with a constant subject,
  * SPO — rows ordered by (subject, predicate, object): resolves
    ``(s ?p ?y)`` patterns — constant subject, *variable* predicate,
  * OSP — rows ordered by (object, subject, predicate): resolves
    ``(?x ?p o)`` patterns — constant object, *variable* predicate.

Range endpoints are found with host-side binary searches over int64
composite keys — O(log N) on a few cached numpy arrays, negligible next to
device work — while the row gathers happen on device from the permuted
stores.  A pattern then costs two binary searches plus one contiguous gather
instead of a full scan + stable sort, and the range *length* gives the
planner an exact cardinality for free.

Each permutation keeps its source-row permutation vector so that overlay
machinery (core/delta.py) can align per-row liveness masks with the sorted
order without re-sorting.

``merge_sorted`` is the compaction primitive: two already-sorted runs of the
same permutation (the base index and a small delta index) interleave into
one sorted array by composite-key binary search — no re-sort of the base.

``TypeIndex`` is the serving-path specialization: the rdf:type subset of
the store ordered by (object, subject), so a batched "members of class C"
request is two binary searches + a slice rather than a full-view sort.
"""
from __future__ import annotations

import itertools
import threading

from dataclasses import dataclass, field

import numpy as np

import jax.numpy as jnp

_SHIFT = np.int64(32)

# StoreIndex identity tokens: device caches (core/delta.py) key their state
# on the *base* they were built from, and Python object ids can be recycled.
_TOKENS = itertools.count()

PERMUTATIONS = ("pos", "pso", "spo", "osp")


INVALID = np.int32(np.iinfo(np.int32).max)


def pow2_bucket(n: int, floor: int = 8) -> int:
    """Smallest power of two >= n (>= floor) — THE capacity-bucket helper.

    Shared by query capacities, delta padding, and member-set padding so
    every layer lands on the same buckets and compiled executables are
    reused across them.
    """
    return 1 << max(int(np.ceil(np.log2(max(n, 1)))), int(np.log2(floor)))


def pad_rows(rows: np.ndarray, cap: int) -> np.ndarray:
    """Pad an (N, 3) triple array to ``cap`` rows of INVALID — THE padding
    helper (delta buckets, materializer batches) so the fill contract
    lives in one place."""
    pad = cap - rows.shape[0]
    if pad <= 0:
        return rows
    return np.concatenate(
        [rows, np.full((pad, 3), INVALID, dtype=np.int32)])


def _composite(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Lexicographic (a, b) order as one sortable int64 key (ids are < 2^31)."""
    return (a.astype(np.int64) << _SHIFT) | b.astype(np.int64)


@dataclass
class _Perm:
    """One sorted permutation: device rows + host search keys + source perm."""

    rows: jnp.ndarray  # device copy of the permuted store
    primary: np.ndarray  # host primary-sort column
    key: np.ndarray  # host (primary << 32 | secondary) composite keys
    perm: np.ndarray  # source-row index of each sorted row
    inv: np.ndarray | None = None  # lazy original-row -> sorted-position map


# (primary, secondary, tertiary) column indices per permutation name; the
# tertiary column breaks ties so exact duplicate rows sort adjacently.
_ORDERS = {"pos": (1, 2, 0), "pso": (1, 0, 2), "spo": (0, 1, 2), "osp": (2, 0, 1)}


def key_cols(name: str):
    """(primary, secondary) column indices of permutation ``name``.

    The device-side key planes of a sorted store are just these two columns
    of its permuted rows — the index-nested-loop join (core/query.py) probes
    them with the pair-search kernel, so no separate key upload ever exists.
    """
    a, b, _ = _ORDERS[name]
    return a, b


@dataclass
class StoreIndex:
    """Sorted permutations of one triple store + host search keys.

    Each permutation is an O(N log N) host lexsort plus a device-resident
    copy of the store, so they materialize lazily on first use: a workload
    of predicate/type patterns (all of LUBM Q1-Q4) never pays for PSO, SPO,
    or OSP.
    """

    _h: np.ndarray = field(repr=False)  # host copy of the store
    _perms: dict = field(default_factory=dict, repr=False)
    token: int = field(default_factory=lambda: next(_TOKENS), repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False,
                                  compare=False)

    @classmethod
    def build(cls, spo) -> "StoreIndex":
        return cls(_h=np.asarray(spo))

    @classmethod
    def from_sorted(cls, rows: np.ndarray, name: str,
                    dev_rows: jnp.ndarray | None = None) -> "StoreIndex":
        """Wrap an array already sorted in permutation ``name`` order.

        Used by compaction: the merged POS run doubles as the new store, so
        the POS permutation is the identity and costs nothing to register.
        ``dev_rows`` hands over an existing device copy (the device-side
        merge result) so the index never re-uploads it.
        """
        idx = cls(_h=np.asarray(rows))
        a, b, _ = _ORDERS[name]
        h = idx._h
        idx._perms[name] = _Perm(
            rows=jnp.asarray(h) if dev_rows is None else dev_rows,
            primary=np.ascontiguousarray(h[:, a]),
            key=_composite(h[:, a], h[:, b]),
            perm=np.arange(h.shape[0], dtype=np.int64),
        )
        return idx

    def perm(self, name: str) -> _Perm:
        if name in self._perms:
            return self._perms[name]
        with self._lock:  # concurrent planners sort and upload it once
            if name not in self._perms:
                a, b, c = _ORDERS[name]
                h = self._h
                p = np.lexsort((h[:, c], h[:, b], h[:, a]))
                hp = h[p]
                self._perms[name] = _Perm(
                    rows=jnp.asarray(hp),
                    primary=np.ascontiguousarray(hp[:, a]),
                    key=_composite(hp[:, a], hp[:, b]),
                    perm=p,
                )
        return self._perms[name]

    def inv_perm(self, name: str) -> np.ndarray:
        """original-row -> sorted-position map of permutation ``name``.

        The device overlay caches (core/delta.py) need it to scatter
        tombstone bits — recorded in original store coordinates — into the
        permuted liveness buffers.  O(N) once per permutation, cached.
        """
        p = self.perm(name)
        if p.inv is None:
            inv = np.empty(p.perm.shape[0], dtype=np.int64)
            inv[p.perm] = np.arange(p.perm.shape[0], dtype=np.int64)
            p.inv = inv
        return p.inv

    # -- legacy aliases (PR 1 API) -------------------------------------------
    @property
    def pos_rows(self) -> jnp.ndarray:
        return self.perm("pos").rows

    @property
    def pso_rows(self) -> jnp.ndarray:
        return self.perm("pso").rows

    @property
    def n(self) -> int:
        return int(self._h.shape[0])

    # -- host-side O(log N) range lookups ------------------------------------
    def primary_range(self, name: str, lo: int, hi: int):
        """Row range of primary-column interval [lo, hi) in permutation ``name``."""
        col = self.perm(name).primary
        r0 = int(np.searchsorted(col, lo, side="left"))
        r1 = int(np.searchsorted(col, hi, side="left"))
        return r0, r1

    def composite_range(self, name: str, a_id: int, blo: int, bhi: int):
        """Row range of (primary == a_id, secondary in [blo, bhi))."""
        key = self.perm(name).key
        r0 = int(np.searchsorted(key, _composite_scalar(a_id, blo)))
        r1 = int(np.searchsorted(key, _composite_scalar(a_id, bhi)))
        return r0, r1

    def p_range(self, plo: int, phi: int):
        """Row range of predicate interval [plo, phi).

        Predicate is the primary sort key of BOTH the POS and PSO
        permutations, so the same (r0, r1) positions are valid in either.
        """
        return self.primary_range("pos", plo, phi)

    def single_p_run(self, r0: int, r1: int):
        """The unique predicate id of POS rows [r0, r1), or None if mixed/empty.

        A LiteMat predicate interval is often wide (free suffix bits) while
        the *store* only contains one predicate id inside it — e.g. rdf:type
        patterns.  Detecting that (O(1) after the range search) upgrades the
        pattern from run-slice + re-check to an exact composite-key range.
        """
        pos_p = self.perm("pos").primary
        if r1 <= r0:
            return None
        if pos_p[r0] == pos_p[r1 - 1]:
            return int(pos_p[r0])
        return None

    def distinct_p_ids(self, plo: int, phi: int, limit: int = 8):
        """Distinct predicate ids the store holds in [plo, phi), or None.

        Walks the sorted POS primary column run-by-run (one binary search
        per distinct id, O(k log N)); gives up past ``limit`` ids — the
        index-nested-loop join probes each id's composite range, so the
        planner only wants this when the id set is small (a LiteMat
        property interval typically covers a handful of sub-properties).
        """
        col = self.perm("pos").primary
        r0, r1 = self.p_range(plo, phi)
        out = []
        while r0 < r1:
            pid = int(col[r0])
            out.append(pid)
            if len(out) > limit:
                return None
            r0 = int(np.searchsorted(col, pid, side="right"))
        return out

    def po_range(self, p_id: int, olo: int, ohi: int):
        """Row range of (p == p_id, o in [olo, ohi)) in POS order."""
        return self.composite_range("pos", p_id, olo, ohi)

    def ps_range(self, p_id: int, slo: int, shi: int):
        """Row range of (p == p_id, s in [slo, shi)) in PSO order."""
        return self.composite_range("pso", p_id, slo, shi)

    def s_range(self, slo: int, shi: int):
        """Row range of subject interval [slo, shi) in SPO order."""
        return self.primary_range("spo", slo, shi)

    def o_range(self, olo: int, ohi: int):
        """Row range of object interval [olo, ohi) in OSP order."""
        return self.primary_range("osp", olo, ohi)


def _composite_scalar(a: int, b: int) -> np.int64:
    return (np.int64(a) << _SHIFT) | np.int64(b)


def merge_sorted(a_rows: np.ndarray, a_key: np.ndarray,
                 b_rows: np.ndarray, b_key: np.ndarray):
    """Interleave two runs sorted by the same composite key -> (rows, key).

    One binary search of the small run against the large one assigns every
    row its merged position — the base run is never re-sorted, so folding a
    delta of M rows into a base of N costs O(M log N + N) instead of the
    O((N+M) log (N+M)) full rebuild.  Rows with equal keys keep a-before-b
    order (stable); intra-key tertiary order is irrelevant to every lookup,
    which searches composite keys only.
    """
    n, m = a_key.shape[0], b_key.shape[0]
    if m == 0:
        return a_rows, a_key
    if n == 0:
        return b_rows, b_key
    pos_b = np.searchsorted(a_key, b_key, side="right") + np.arange(m)
    out_rows = np.empty((n + m, a_rows.shape[1]), dtype=a_rows.dtype)
    out_key = np.empty(n + m, dtype=np.int64)
    mask_b = np.zeros(n + m, dtype=bool)
    mask_b[pos_b] = True
    out_rows[pos_b] = b_rows
    out_key[pos_b] = b_key
    out_rows[~mask_b] = a_rows
    out_key[~mask_b] = a_key
    return out_rows, out_key


@dataclass
class TypeIndex:
    """rdf:type triples ordered by (object, subject) — the serving Q1 index.

    A class-membership request for concept interval [lo, hi) is resolved by
    two host binary searches over the object column; the subjects of the hit
    run sit in one contiguous device slice (sorted by object, then subject —
    NOT globally deduplicated: an instance carrying several types inside the
    interval appears once per type, so DISTINCT still needs a per-request
    dedup over the *slice*, which is bounded by the class size rather than
    the whole type view).
    """

    subj: jnp.ndarray  # int32[T+1] subjects, (o, s) order + INVALID sentinel
    obj: jnp.ndarray  # int32[T+1] objects, (o, s) order + INVALID sentinel
    _h_obj: np.ndarray = field(repr=False)  # true (unpadded) object column

    @classmethod
    def build(cls, spo, type_id: int) -> "TypeIndex":
        h = np.asarray(spo)
        m = h[:, 1] == np.int32(type_id)
        s, o = h[m, 0], h[m, 2]
        perm = np.lexsort((s, o))
        s, o = s[perm], o[perm]
        # one INVALID sentinel keeps device gathers well-formed when the
        # store has no type triples at all
        pad = np.full(1, np.iinfo(np.int32).max, np.int32)
        return cls(subj=jnp.asarray(np.concatenate([s, pad])),
                   obj=jnp.asarray(np.concatenate([o, pad])),
                   _h_obj=np.ascontiguousarray(o))

    @property
    def n(self) -> int:
        return int(self._h_obj.shape[0])

    def range_of(self, lo: int, hi: int):
        """(start, length) of the object interval [lo, hi)."""
        r0 = int(np.searchsorted(self._h_obj, lo, side="left"))
        r1 = int(np.searchsorted(self._h_obj, hi, side="left"))
        return r0, r1 - r0
