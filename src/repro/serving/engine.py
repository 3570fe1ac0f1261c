"""Batched query serving — LiteMat as an online inference service.

The paper's query processor evaluates one SPARQL query per Spark job.  A
serving deployment instead sees *streams* of parameterized queries ("all
members of class C", "all x with x:C and (x p y)") that share a plan and
differ only in constants.  Because LiteMat turns inference into interval
compares, a parameterized plan is a pure tensor function of (lo, hi) pairs —
a whole batch executes as ONE vmapped XLA call over the store.

Request resolution rides the (object, subject)-sorted type index
(core/index.py): a class interval [lo, hi) is two host binary searches +
one contiguous device slice, so per-request work is bounded by the *largest
class in the batch* (bucketed to a power of two), not the type view.
Answer semantics are DISTINCT subjects (SPARQL set semantics, matching the
QueryEngine oracle): an instance can legitimately carry several MSC types
inside the queried interval (e.g. Chair + FullProfessor under Professor),
so each request still deduplicates its own slice — a sort over the slice,
never over the view.

View freshness is automatic: every serving call compares the monotonic
``KnowledgeBase.version`` counter against the version its views were built
at and rebuilds them when the store has changed — ``insert`` / ``delete`` /
``compact`` need no manual invalidation.  ``invalidate()`` remains for the
one case the counter cannot see: direct (out-of-API) mutation of a store
field.

:class:`ShardedQueryServer` is the multi-device deployment of the same
plans over a :class:`~repro.core.shard.ShardedKB`: every shard keeps its
own type index and property view (class-membership subjects are co-hashed
— derived ``(x rdf:type C)`` rows live on ``shard(x)`` — so per-shard
distinct sets are DISJOINT), a batch fans out through ``shard_map`` (vmap
with fewer devices than shards), and the per-shard answers merge by
summing distinct counts and merge-sorting the per-shard member lists.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from functools import partial

from jax.sharding import AxisType, PartitionSpec as P

from repro.core.engine import KnowledgeBase
from repro.core.index import TypeIndex
from repro.kernels import ops

INVALID = jnp.int32(np.iinfo(np.int32).max)


def _distinct_count_topk(hits, topk: int):
    """Sorted-dedup count + first-k distinct values of INVALID-padded hits."""
    h = jnp.sort(hits, stable=False)
    first = jnp.concatenate([jnp.ones((1,), bool), h[1:] != h[:-1]])
    uniq = first & (h != INVALID)
    count = uniq.astype(jnp.int32).sum()
    vals = jnp.where(uniq, h, INVALID)
    top = -jax.lax.top_k(-vals, topk)[0]
    return count, jnp.where(top == INVALID, -1, top)


def _slice_hits(subj_os, start_row, len_row, cap: int):
    """Gather one request's type-index segments (primary + spill intervals)."""
    src, ok, _, _ = ops.segment_positions(start_row, len_row, cap)
    return jnp.where(ok, subj_os[jnp.clip(src, 0, subj_os.shape[0] - 1)],
                     INVALID)


def _members_shard(subj_os, starts, lens, cap: int, topk: int):
    """One store's batched Q1 plan (vmapped over the request axis)."""

    def one(start_row, len_row):
        return _distinct_count_topk(
            _slice_hits(subj_os, start_row, len_row, cap), topk)

    return jax.vmap(one)(starts, lens)


def _prop_join_shard(subj_os, ps_sorted, p_sorted, starts, lens,
                     plo, phi, cap: int, topk: int, kp: int):
    """One store's batched Q3 plan: x:C ⋈ (x p y) semi-join per request.

    The type side is an index slice; ``ps_sorted`` are property-triple
    subjects pre-sorted by (s, p) once per store, so each sliced subject
    semi-joins with one binary search per property interval (kp of them:
    primary + spills, usually 1).
    """

    from repro.utils import pair64

    def one(start_row, len_row, plo_row, phi_row):
        hits = _slice_hits(subj_os, start_row, len_row, cap)
        # rows are sorted by the (subject, predicate) composite, so the first
        # row >= (s, plo) decides the semi-join: it matches iff its subject
        # is s and its predicate is still < phi (contiguous interval run).
        hit = jnp.zeros(hits.shape, bool)
        for i in range(kp):
            X = pair64.searchsorted_pair(
                ps_sorted, p_sorted, hits,
                jnp.full(hits.shape, plo_row[i], jnp.int32), side="left",
            )
            Xc = jnp.clip(X, 0, ps_sorted.shape[0] - 1)
            hit = hit | ((ps_sorted[Xc] == hits) & (p_sorted[Xc] < phi_row[i]))
        return _distinct_count_topk(jnp.where(hit, hits, INVALID), topk)

    return jax.vmap(one)(starts, lens, plo, phi)


@partial(jax.jit, static_argnames=("cap", "topk"))
def _serve_class_members(subj_os, starts, lens, cap: int, topk: int):
    """vmapped Q1 plan over index slices: (B, k) ranges -> counts + members."""
    return _members_shard(subj_os, starts, lens, cap, topk)


@partial(jax.jit, static_argnames=("cap", "topk", "kp"))
def _serve_class_prop_join(subj_os, ps_sorted, p_sorted, starts, lens,
                           plo, phi, cap: int, topk: int, kp: int):
    """vmapped Q3 plan: x:C ⋈ (x p y) -> distinct-x counts + bindings."""
    return _prop_join_shard(subj_os, ps_sorted, p_sorted, starts, lens,
                            plo, phi, cap, topk, kp)


@dataclass
class QueryServer:
    """Compile-once, serve-batches facade over a KnowledgeBase."""

    K: KnowledgeBase
    topk: int = 32
    _views: dict = field(default_factory=dict)
    _seen_version: int | None = field(default=None)

    @property
    def served_version(self) -> int | None:
        """Store version the current views were (re)built at — what an
        answer returned right now is consistent with."""
        return self._seen_version

    def invalidate(self):
        """Drop derived views/indexes after an out-of-API store mutation.

        ``insert`` / ``delete`` / ``compact`` bump ``K.version`` and are
        picked up automatically; this only matters when a store field was
        swapped directly (tests, manual surgery).
        """
        self._views.clear()
        self._seen_version = self.K.version

    def _sync(self):
        """Rebuild every derived view atomically against ONE store version.

        The old pattern — compare the version, clear, let views rebuild
        lazily on first use — raced the writer: the type index could build
        at version v and the property view at v+1, silently mixing two
        stores in one batch.  Now a detected change rebuilds ALL views
        eagerly under the store's write lock (writers are excluded, so the
        version provably cannot move between the capture and the builds);
        the version-equality fast path stays lock-free.
        """
        if self._seen_version == self.K.version:
            return
        with self.K.write_lock:
            v = self.K.version
            self._views.clear()
            self._build_views()
            self._seen_version = v

    def _build_views(self):
        """Eagerly materialize every derived view (write lock held)."""
        self._type_index()
        self._prop_view()

    def _store(self):
        """The live lite store (base ∪ delta, tombstones dropped)."""
        return self.K.store_rows("litemat")

    def _type_index(self) -> TypeIndex:
        if "type_os" not in self._views:
            self._views["type_os"] = TypeIndex.build(
                self._store(), int(self.K.dtb.rdf_type_id))
        return self._views["type_os"]

    def _prop_view(self):
        """Property triples sorted by (subject, predicate)."""
        if "prop" not in self._views:
            spo = np.asarray(self._store())
            m = spo[:, 1] != self.K.dtb.rdf_type_id
            s, p = spo[m, 0], spo[m, 1]
            order = np.lexsort((p, s))
            self._views["prop"] = (jnp.asarray(s[order]), jnp.asarray(p[order]))
        return self._views["prop"]

    def _intervals(self, names, enc):
        """Per name: primary + spill [lo, hi) intervals, 0-padded to (B, k).

        Spill intervals carry the secondary-edge subsumees under multiple
        inheritance; dropping them would silently undercount (the
        QueryEngine oracle honors them, so the server must too).
        """
        per = []
        for n in names:
            (lo, hi), spills = enc.interval_of(n)
            per.append([(int(lo), int(hi))] + [(int(a), int(b))
                                               for a, b in spills])
        k = max(len(p) for p in per) if per else 1
        lo = np.zeros((len(names), k), np.int32)
        hi = np.zeros((len(names), k), np.int32)
        for i, p in enumerate(per):
            for j, (a, b) in enumerate(p):
                lo[i, j], hi[i, j] = a, b
        return lo, hi

    def _ranges(self, class_names):
        """Host-side index lookups: (starts, lens (B, k), capacity bucket)."""
        ti = self._type_index()
        clo, chi = self._intervals(class_names, self.K.kb.tbox.concepts)
        starts = np.zeros(clo.shape, np.int32)
        lens = np.zeros(clo.shape, np.int32)
        for i in range(clo.shape[0]):
            for j in range(clo.shape[1]):
                starts[i, j], lens[i, j] = ti.range_of(int(clo[i, j]),
                                                       int(chi[i, j]))
        from repro.core.query import _pow2

        longest = max(int(lens.sum(axis=1).max()) if lens.size else 1,
                      self.topk, 1)
        cap = _pow2(longest, floor=1)
        return ti, jnp.asarray(starts), jnp.asarray(lens), cap

    def class_members(self, class_names):
        """Batch of Q1-style requests -> (distinct counts, member ids)."""
        self._sync()
        ti, starts, lens, cap = self._ranges(class_names)
        counts, members = _serve_class_members(ti.subj, starts, lens, cap,
                                               self.topk)
        return np.asarray(counts), np.asarray(members)

    def class_prop_join(self, class_names, prop_names):
        """Batch of Q3-style requests -> (distinct-x counts, x bindings)."""
        self._sync()
        ti, starts, lens, cap = self._ranges(class_names)
        ps, pp = self._prop_view()
        plo, phi = self._intervals(prop_names, self.K.kb.tbox.properties)
        counts, subs = _serve_class_prop_join(
            ti.subj, ps, pp, starts, lens, jnp.asarray(plo), jnp.asarray(phi),
            cap, self.topk, kp=int(plo.shape[1]),
        )
        return np.asarray(counts), np.asarray(subs)


# ---------------------------------------------------------------------------
# Sharded serving: per-shard fan-out + distinct-count merge
# ---------------------------------------------------------------------------


def _merge_members(members, topk: int):
    """Merge per-shard ascending member lists into the global smallest-topk.

    Subjects are co-hashed, so the per-shard distinct sets are disjoint and
    a merge-sort of the per-shard topk lists IS the global topk.  ``-1``
    padding maps through INVALID so it sorts last.
    """
    S, B, _ = members.shape
    m = jnp.where(members < 0, INVALID, members)
    m = jnp.transpose(m, (1, 0, 2)).reshape(B, -1)
    m = jnp.sort(m, axis=1, stable=False)[:, :topk]
    return jnp.where(m == INVALID, -1, m)


def _pad_plane(arrs: list, fill) -> np.ndarray:
    """Stack 1-D arrays of unequal length into [S, max] with a fill tail."""
    cap = max(a.shape[0] for a in arrs)
    out = np.full((len(arrs), cap), fill, arrs[0].dtype)
    for i, a in enumerate(arrs):
        out[i, :a.shape[0]] = a
    return out


@dataclass
class ShardedQueryServer:
    """Compile-once, serve-batches facade over a ShardedKB.

    Identical request/answer contract to :class:`QueryServer` — counts and
    member lists are pinned equal in tests — but the device work fans out
    per shard: the batch's index ranges resolve against every shard's own
    type index, the stacked plans execute through ``shard_map`` when a
    device per shard exists (vmap otherwise — same math, one device), and
    the per-shard answers merge by summing counts (disjoint distinct sets)
    and merge-sorting member lists.
    """

    K: object  # ShardedKB
    topk: int = 32
    use_shard_map: bool | None = None
    _views: dict = field(default_factory=dict)
    _fans: dict = field(default_factory=dict, repr=False)
    _seen_version: int | None = field(default=None)

    @property
    def served_version(self) -> int | None:
        """Store version the current views were (re)built at."""
        return self._seen_version

    def invalidate(self):
        self._views.clear()
        self._seen_version = self.K.version

    def _sync(self):
        """Atomic resync — same contract as :meth:`QueryServer._sync`."""
        if self._seen_version == self.K.version:
            return
        with self.K.write_lock:
            v = self.K.version
            self._views.clear()
            self._build_views()
            self._seen_version = v

    def _build_views(self):
        """Eagerly materialize every derived view (write lock held)."""
        tis = self._type_indexes()
        self._prop_views()
        if "subj" not in self._views:
            self._views["subj"] = jnp.asarray(_pad_plane(
                [np.asarray(ti.subj) for ti in tis],
                np.int32(np.iinfo(np.int32).max)))

    def _sm(self) -> bool:
        if self.use_shard_map is not None:
            return self.use_shard_map
        return jax.local_device_count() >= self.K.n_shards > 1

    def _type_indexes(self):
        if "type_os" not in self._views:
            self.K._flush("litemat")
            tid = int(self.K.dtb.rdf_type_id)
            self._views["type_os"] = [
                TypeIndex.build(np.asarray(K.store_rows("litemat")), tid)
                for K in self.K.shards]
        return self._views["type_os"]

    def _prop_views(self):
        if "prop" not in self._views:
            self.K._flush("litemat")
            tid = self.K.dtb.rdf_type_id
            ps, pp = [], []
            for K in self.K.shards:
                spo = np.asarray(K.store_rows("litemat"))
                m = spo[:, 1] != tid
                s, p = spo[m, 0], spo[m, 1]
                order = np.lexsort((p, s))
                ps.append(s[order])
                pp.append(p[order])
            self._views["prop"] = (
                jnp.asarray(_pad_plane(ps, np.int32(np.iinfo(np.int32).max))),
                jnp.asarray(_pad_plane(pp, np.int32(np.iinfo(np.int32).max))))
        return self._views["prop"]

    _intervals = QueryServer._intervals  # same host-side interval resolution

    def _ranges(self, class_names):
        """Per-shard index lookups -> stacked (subj, starts, lens, cap)."""
        tis = self._type_indexes()
        clo, chi = self._intervals(class_names, self.K.kb.tbox.concepts)
        S, B, k = len(tis), clo.shape[0], clo.shape[1]
        starts = np.zeros((S, B, k), np.int32)
        lens = np.zeros((S, B, k), np.int32)
        for si, ti in enumerate(tis):
            for i in range(B):
                for j in range(k):
                    starts[si, i, j], lens[si, i, j] = ti.range_of(
                        int(clo[i, j]), int(chi[i, j]))
        from repro.core.query import _pow2

        longest = max(
            int(lens.sum(axis=2).max()) if lens.size else 1, self.topk, 1)
        cap = _pow2(longest, floor=1)
        if "subj" not in self._views:
            self._views["subj"] = jnp.asarray(_pad_plane(
                [np.asarray(ti.subj) for ti in tis],
                np.int32(np.iinfo(np.int32).max)))
        return (self._views["subj"], jnp.asarray(starts), jnp.asarray(lens),
                cap)

    def _fan_members(self, subj, starts, lens, cap: int):
        """Stacked per-shard Q1 execution: shard_map or vmap fan-out."""
        key = ("members", cap, self.topk, self._sm())
        fn = self._fans.get(key)
        if fn is None:
            if self._sm():
                mesh = jax.make_mesh((self.K.n_shards,), ("shard",),
                                     axis_types=(AxisType.Auto,))

                def body(su, st, ln):
                    c, m = _members_shard(su[0], st[0], ln[0], cap, self.topk)
                    return c[None], m[None]

                fn = jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=(P("shard"),) * 3,
                    out_specs=(P("shard"),) * 2, check_vma=False))
            else:
                fn = jax.jit(jax.vmap(
                    lambda su, st, ln: _members_shard(
                        su, st, ln, cap, self.topk)))
            self._fans[key] = fn
        return fn(subj, starts, lens)

    def _fan_prop_join(self, subj, ps, pp, starts, lens, plo, phi,
                       cap: int, kp: int):
        key = ("propjoin", cap, self.topk, kp, self._sm())
        fn = self._fans.get(key)
        if fn is None:
            if self._sm():
                mesh = jax.make_mesh((self.K.n_shards,), ("shard",),
                                     axis_types=(AxisType.Auto,))

                def body(su, s_, p_, st, ln, lo, hi):
                    c, m = _prop_join_shard(
                        su[0], s_[0], p_[0], st[0], ln[0], lo[0], hi[0],
                        cap, self.topk, kp)
                    return c[None], m[None]

                fn = jax.jit(jax.shard_map(
                    body, mesh=mesh, in_specs=(P("shard"),) * 7,
                    out_specs=(P("shard"),) * 2, check_vma=False))
            else:
                fn = jax.jit(jax.vmap(
                    lambda su, s_, p_, st, ln, lo, hi: _prop_join_shard(
                        su, s_, p_, st, ln, lo, hi, cap, self.topk, kp)))
            self._fans[key] = fn
        return fn(subj, ps, pp, starts, lens, plo, phi)

    def class_members(self, class_names):
        """Batched Q1: fan out per shard, sum counts, merge member lists."""
        self._sync()
        subj, starts, lens, cap = self._ranges(class_names)
        counts, members = self._fan_members(subj, starts, lens, cap)
        return (np.asarray(counts.sum(axis=0)),
                np.asarray(_merge_members(members, self.topk)))

    def class_prop_join(self, class_names, prop_names):
        """Batched Q3: the semi-join is fully shard-local (co-hashed x)."""
        self._sync()
        subj, starts, lens, cap = self._ranges(class_names)
        ps, pp = self._prop_views()
        plo, phi = self._intervals(prop_names, self.K.kb.tbox.properties)
        S = self.K.n_shards
        plo_s = jnp.broadcast_to(jnp.asarray(plo), (S, *plo.shape))
        phi_s = jnp.broadcast_to(jnp.asarray(phi), (S, *phi.shape))
        counts, subs = self._fan_prop_join(
            subj, ps, pp, starts, lens, plo_s, phi_s, cap,
            kp=int(plo.shape[1]))
        return (np.asarray(counts.sum(axis=0)),
                np.asarray(_merge_members(subs, self.topk)))
