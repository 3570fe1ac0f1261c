"""KnowledgeBase facade: raw triples -> encoded -> materialized -> queryable.

One object wires the whole LiteMat pipeline and exposes the three execution
modes of the paper's evaluation (lite / full / no materialization), plus the
paper's appendix queries Q1–Q4 as canned pattern lists.

Beyond the paper's batch pipeline, the KnowledgeBase is *live*: LiteMat's
interval encoding reserves unused id headroom exactly so the dictionary and
stores can grow without re-encoding, and ``insert`` / ``delete`` exploit
that:

  * ``insert(raw)``  — new instance terms extend the parallel dictionary in
    place (ids past ``n_instance_terms``; no existing id moves), and the
    encoded rows land in an append-only delta overlay (core/delta.py) that
    queries union with the base via sorted delta indexes.  Lite/full
    materialization of the delta is LAZY per mode: each store derives its
    backlog the first time it is served, so single-mode deployments run
    one materializer per insert, not two.
  * ``delete(raw)``  — tombstones the raw rows, then repairs the
    materialized stores exactly by re-deriving the affected instances from
    their remaining live triples (core/update.py).
  * ``compact()``    — folds the overlay into the base stores with one
    sorted-merge pass per index permutation; triggered automatically once
    the delta-to-base ratio passes ``compact_threshold``.

Every mutation bumps the monotonic ``version`` counter; query engines and
the serving layer (serving/engine.py) re-sync their views off it, so there
is no manual invalidation step.
"""
from __future__ import annotations

import contextvars
import threading
from dataclasses import dataclass, field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.abox import EncodedKB, encode_obe, encode_sae
from repro.core.closure import full_materialize
from repro.core.delta import (
    MODES, DeltaKB, DeviceStoreCache, StoreView, compact_view,
)
from repro.core.index import StoreIndex
from repro.core.materialize import DeviceTBox, compact_rows, lite_materialize
from repro.core.query import Pattern, QueryEngine
from repro.core.tbox import TBox, build_tbox
from repro.core.update import (
    DynamicDictionary, RowLocator, absorb_new_terms, affected_instances,
    encode_delta, materialize_delta_mode, mention_rows, mentions_mask,
)
from repro.obs import trace as obs_trace
from repro.rdf.generator import RawDataset
from repro.testing import faults
from repro.utils.parallel import run_concurrently

# The paper's appendix queries (over the LUBM vocabulary).
PAPER_QUERIES = {
    "Q1": [Pattern("?x", "rdf:type", "Professor")],
    "Q2": [Pattern("?x", "memberOf", "?y")],
    "Q3": [Pattern("?x", "rdf:type", "Professor"), Pattern("?x", "memberOf", "?y")],
    "Q4": [
        Pattern("?x", "rdf:type", "Chair"),
        Pattern("?y", "rdf:type", "Department"),
        Pattern("?x", "worksFor", "?y"),
    ],
}


def _raw_columns(raw):
    """RawDataset | (s, p, o) arrays -> (s_fp, p_fp, o_fp, term_strings)."""
    if isinstance(raw, RawDataset) or hasattr(raw, "s"):
        return (np.asarray(raw.s), np.asarray(raw.p), np.asarray(raw.o),
                getattr(raw, "term_strings", None))
    s, p, o = raw
    return np.asarray(s), np.asarray(p), np.asarray(o), None


@dataclass
class KnowledgeBase:
    kb: EncodedKB
    dtb: DeviceTBox
    lite_spo: jnp.ndarray  # compacted lite-materialized base store
    full_spo: jnp.ndarray  # compacted fully-materialized base store
    lite_stats: dict
    full_stats: dict
    compact_threshold: float = 0.25  # auto-compact past this delta ratio
    version: int = 0  # bumps on every insert/delete/compact
    lazy_materialize: bool = True  # derive lite/full deltas per served mode
    mat_counts: dict = field(
        default_factory=lambda: {"litemat": 0, "full": 0})  # batches derived
    _engines: dict = field(default_factory=dict, repr=False)
    _delta: DeltaKB | None = field(default=None, repr=False)
    _dyn: DynamicDictionary | None = field(default=None, repr=False)
    _base_indexes: dict = field(default_factory=dict, repr=False)
    _views: dict = field(default_factory=dict, repr=False)
    _raw_loc: RowLocator | None = field(default=None, repr=False)
    _dev_caches: dict = field(default_factory=dict, repr=False)
    _pending_raw: list = field(default_factory=list, repr=False)
    _mat_cursor: dict = field(
        default_factory=lambda: {"litemat": 0, "full": 0}, repr=False)
    # writers (insert/delete/compact) serialize here; snapshot captures
    # (core/snapshot.py) take it briefly to see a quiescent version
    write_lock: threading.RLock = field(
        default_factory=threading.RLock, repr=False, compare=False)

    @classmethod
    def build(cls, raw: RawDataset, tbox: TBox | None = None,
              parallel_tbox: bool = False) -> "KnowledgeBase":
        tbox = tbox or build_tbox(raw.onto, parallel=parallel_tbox)
        kb = encode_obe(raw, tbox)
        dtb = DeviceTBox.build(tbox)

        def derive(materialize):
            rows, valid, stats = materialize(kb, dtb)
            return compact_rows(rows, valid), stats

        # the two stores derive from threads so their compiles overlap
        (lite, lstats), (full, fstats) = run_concurrently(
            [partial(derive, lite_materialize),
             partial(derive, full_materialize)])
        return cls(kb=kb, dtb=dtb, lite_spo=lite, full_spo=full,
                   lite_stats=lstats, full_stats=fstats)

    # -- store plumbing ------------------------------------------------------
    def _base_store(self, mode: str) -> jnp.ndarray:
        return {
            "litemat": self.lite_spo,
            "full": self.full_spo,
            "rewrite": self.kb.spo,
        }[mode]

    def _base_index(self, mode: str) -> StoreIndex:
        if mode not in self._base_indexes:
            self._base_indexes[mode] = StoreIndex.build(self._base_store(mode))
        return self._base_indexes[mode]

    @property
    def delta(self) -> DeltaKB:
        if self._delta is None:
            self._delta = DeltaKB()
        return self._delta

    def dev_cache(self, mode: str) -> DeviceStoreCache:
        """The store's persistent device buffers (survive version bumps)."""
        if mode not in self._dev_caches:
            self._dev_caches[mode] = DeviceStoreCache()
        return self._dev_caches[mode]

    def _flush_mat(self, *modes: str) -> None:
        """Materialize pending insert batches for the given derived modes.

        Inserts only queue their encoded raw rows (``lazy_materialize``);
        the first time a mode is actually *served* — a view build, a
        delete's repair, a compaction — its share of the queue is derived
        here.  A lite-only deployment therefore never runs the full
        closure of its inserts (and vice versa).

        Crash-atomic per mode: every pending batch is derived BEFORE any
        of them is appended, so a failure mid-derivation (fault site
        ``engine.flush_mat``) leaves the log and cursor untouched — the
        published store stays consistent and a later flush simply retries
        the whole backlog.  The modes derive concurrently (their compiles
        overlap) and commit in order: a fault in one mode leaves the modes
        before it committed, as a mode-by-mode flush would.
        """
        n = len(self._pending_raw)
        todo, fault = [], None
        for mode in modes:
            cur = self._mat_cursor[mode]
            if cur >= n:
                continue
            try:
                for b in range(cur, n):
                    faults.fire("engine.flush_mat", mode=mode, batch=b)
            except Exception as e:  # the modes before this one still land
                fault = e
                break
            todo.append((mode, cur))

        def derive(mode, cur):
            with obs_trace.span("engine.flush", mode=mode, n_batches=n - cur):
                return [materialize_delta_mode(spo, self.dtb, mode)
                        for spo in self._pending_raw[cur:]]

        # each thread derives inside a copy of this context: its span joins
        # the calling request's trace
        results = run_concurrently([
            partial(contextvars.copy_context().run, derive, m, c)
            for m, c in todo])
        for (mode, _), derived in zip(todo, results):
            for rows in derived:
                self.delta.log(mode).append(rows)
                self.mat_counts[mode] += 1
            self._mat_cursor[mode] = n
        if fault is not None:
            raise fault
        if self._pending_raw and all(
                c >= n for c in self._mat_cursor.values()):
            self._pending_raw.clear()
            self._mat_cursor = {m: 0 for m in self._mat_cursor}

    def _pending_rows(self, mode: str) -> int:
        """Raw rows queued for ``mode`` whose derivation hasn't run yet."""
        if mode not in self._mat_cursor:
            return 0
        return sum(int(b.shape[0])
                   for b in self._pending_raw[self._mat_cursor[mode]:])

    def view(self, mode: str) -> StoreView:
        """The live base+delta StoreView of one store, cached per version."""
        key = (mode, self.version)
        if key not in self._views:
            if mode in ("litemat", "full"):
                self._flush_mat(mode)
            idx = self._base_index(mode)
            if self._delta is None or self._delta.empty:
                v = StoreView(base_rows=self._base_store(mode), base_h=idx._h,
                              base_index=idx, cache=self.dev_cache(mode))
            else:
                v = StoreView.overlay(self._base_store(mode), idx,
                                      self._delta.log(mode),
                                      self._delta.base_alive[mode],
                                      cache=self.dev_cache(mode),
                                      kills=tuple(self._delta.kills[mode]))
            self._views[key] = v
        return self._views[key]

    def store_rows(self, mode: str = "litemat") -> jnp.ndarray:
        """Effective (live) rows of one store — what serving snapshots."""
        if self._delta is None or self._delta.empty:
            return self._base_store(mode)
        return jnp.asarray(self.view(mode).live_rows())

    def engine(self, mode: str = "litemat", use_index: bool = True) -> QueryEngine:
        """Cached QueryEngine per (mode, use_index), re-synced to ``version``.

        ``use_index=False`` forces the scan-only path — the oracle the
        indexed executables are validated against (tests/benchmarks).
        """
        key = (mode, use_index)
        v = self.view(mode)
        eng = self._engines.get(key)
        if eng is None:
            eng = QueryEngine(kb=self.kb, spo=self._base_store(mode),
                              mode=mode, dtb=self.dtb, use_index=use_index,
                              view=v)
            self._engines[key] = eng
        elif eng.view is not v:
            eng.set_view(v)
        return eng

    def query(self, patterns, select=None, mode: str = "litemat",
              use_index: bool = True):
        rows, sel = self.engine(mode, use_index).run(patterns, select=select)
        return rows, sel

    def answers(self, patterns, select=None, mode: str = "litemat",
                use_index: bool = True) -> set:
        rows, _ = self.query(patterns, select=select, mode=mode,
                             use_index=use_index)
        return {tuple(r) for r in rows.tolist()}

    def prewarm(self, queries=None, modes=("litemat",), buckets=(),
                use_index: bool = True, selects=None) -> int:
        """Pre-trace executables for ``queries`` (default: Q1–Q4) in every
        mode, compiling them concurrently; returns #plans compiled.
        ``selects`` gives each query's projection (default: all vars)."""
        queries = (list(queries) if queries is not None
                   else list(PAPER_QUERIES.values()))
        engines = [self.engine(m, use_index) for m in modes]
        before = sum(e.cache_stats["misses"] for e in engines)
        run_concurrently([c for e in engines for c in e.prewarm_calls(
            queries, buckets=buckets, selects=selects)])
        return sum(e.cache_stats["misses"] for e in engines) - before

    def warm_device(self, mode: str = "litemat", keys=("scan", "pos")):
        """Bring ``mode``'s device buffers up to the current version.

        The post-mutation warmup unit: with plans prewarmed, this is ALL
        the work a first query pays after an insert/delete beyond the query
        itself — O(delta) bucket refresh + O(#killed) tombstone scatters,
        independent of the base size (``dev_cache(mode).stats`` has the
        transfer accounting).
        """
        return self.view(mode).warm_device(keys)

    # -- device resource accounting (obs/ledger.py feed) ---------------------
    def device_buffers(self) -> list:
        """Every device buffer this store references, as ledger records.

        ``(component, buffer id, nbytes)`` per buffer: base store arrays
        and materialized permutations under ``base``, pow2 delta buckets
        under ``delta``, liveness masks under ``alive``, the replicated
        TBox planes under ``tbox``.  Ids let the ledger dedupe arrays
        shared between owners (a compacted POS permutation IS the store
        array; a pinned snapshot references the same base).  Walks only
        existing state — never materializes a view or flushes a delta.
        """
        out = []
        for spo in (self.kb.spo, self.lite_spo, self.full_spo):
            out.append(("base", id(spo), spo.nbytes))
        for idx in self._base_indexes.values():
            for p in idx._perms.values():
                out.append(("base", id(p.rows), p.rows.nbytes))
        for cache in self._dev_caches.values():
            out.extend(cache.device_buffers())
        for v in self._views.values():
            out.extend(v.device_buffers())
        for a in vars(self.dtb).values():
            if hasattr(a, "nbytes") and hasattr(a, "shape"):
                out.append(("tbox", id(a), a.nbytes))
        return out

    def n_live_triples(self) -> int:
        """Live triples in the served (litemat) store, side-effect-free.

        Counts base rows minus tombstones plus live delta rows plus
        pending (not-yet-materialized) insert batches — deliberately NOT
        through ``view()``, which would flush materialization from inside
        a telemetry sampler.
        """
        d = self._delta
        if d is None:
            n = int(self.lite_spo.shape[0])
        else:
            alive = d.base_alive["litemat"]
            n = (int(self.lite_spo.shape[0]) if alive is None
                 else int(alive.sum()))
            n += d.logs["litemat"].n_live
        return n + self._pending_rows("litemat")

    def track_ledger(self, shard="0") -> None:
        """Register with the process ledger (idempotent, weakly held)."""
        if getattr(self, "_ledger_handle", None) is None:
            from repro.obs.ledger import LEDGER

            self._ledger_handle = LEDGER.track(shard, self)

    def sizes(self) -> dict:
        out = dict(
            original=self.kb.n,
            lite=int(self.lite_spo.shape[0]),
            full=int(self.full_spo.shape[0]),
        )
        if self._delta is not None and not self._delta.empty:
            out["delta_rows"] = sum(
                self._delta.n_rows(m) for m in MODES)
            pending = sum(self._pending_rows(m) for m in ("litemat", "full"))
            if pending:
                out["delta_rows_pending_mat"] = pending
        return out

    # -- incremental updates -------------------------------------------------
    def _dynamic(self) -> DynamicDictionary:
        if self._dyn is None:
            self._dyn = DynamicDictionary.from_kb(self.kb)
        return self._dyn

    def _raw_locator(self) -> RowLocator:
        if self._raw_loc is None:
            self._raw_loc = RowLocator.build(self._base_index("rewrite")._h)
        return self._raw_loc

    def _bump(self) -> None:
        self.version += 1
        self._views.clear()

    @property
    def delta_ratio(self) -> float:
        if self._delta is None and not self._pending_raw:
            return 0.0
        # pending (not yet derived) insert batches count once per lazy mode:
        # the raw row count is the cheap proxy for the rows their derivation
        # will add, so auto-compaction triggers on the same schedule whether
        # or not the modes have been served yet.
        extra = sum(self._pending_rows(m) for m in ("litemat", "full"))
        return self.delta.ratio({
            "rewrite": self.kb.n,
            "litemat": int(self.lite_spo.shape[0]),
            "full": int(self.full_spo.shape[0]),
        }, extra_rows=extra)

    def insert(self, raw, auto_compact: bool = True) -> dict:
        """Append raw triples without rebuilding: encode + queue derivation.

        New instance/literal terms extend the dictionary in place (ids past
        ``n_instance_terms``); predicates must be TBox properties (the TBox
        is fixed between full re-encodes).  The encoded rows land in the raw
        delta log immediately; their lite/full materialization is *lazy* —
        derived the first time each mode is actually served (``view``,
        ``delete``, ``compact``) — so single-mode deployments only ever run
        one materializer per insert.
        """
        s_fp, p_fp, o_fp, strings = _raw_columns(raw)
        if s_fp.shape[0] == 0:
            return dict(n_inserted=0, n_new_terms=0)
        with self.write_lock:
            dyn = self._dynamic()
            spo, n_new = encode_delta(dyn, s_fp, p_fp, o_fp)
            absorb_new_terms(self.kb, dyn, strings)
            d = self.delta
            d.log("rewrite").append(spo)
            self._pending_raw.append(spo)
            if not self.lazy_materialize:
                self._flush_mat("litemat", "full")
            d.n_new_terms += n_new
            self._bump()
            stats = dict(
                n_inserted=int(spo.shape[0]),
                n_new_terms=n_new,
                n_pending_mat=sum(
                    self._pending_rows(m) for m in ("litemat", "full")),
                delta_ratio=round(self.delta_ratio, 4),
                version=self.version,
            )
            if auto_compact and self.delta_ratio > self.compact_threshold:
                stats["compacted"] = self.compact()
            return stats

    # -- sharded-reusable delete primitives (core/shard.py orchestrates the
    # same three steps across shards; KnowledgeBase.delete below composes
    # them into the single-store delete) --------------------------------------
    def append_raw(self, rows: np.ndarray) -> None:
        """Append pre-encoded raw rows to the rewrite delta log (no bump)."""
        self.delta.log("rewrite").append(rows)

    def append_derived(self, mode: str, rows: np.ndarray) -> None:
        """Append pre-derived rows to one materialized store's delta log."""
        if rows.shape[0]:
            self.delta.log(mode).append(rows)

    def kill_raw_rows(self, q: np.ndarray) -> np.ndarray:
        """Tombstone exact encoded triples in the raw store (base + delta).

        Returns the rows actually killed (live copies only); does NOT
        repair the derived stores — callers follow up with
        ``kill_derived_mentions`` + re-derivation of the affected
        instances' ``live_raw_mentions``.
        """
        d = self.delta
        deleted = []
        base_h = self._base_index("rewrite")._h
        hits = self._raw_locator().find(q)
        if hits.size:
            alive = d.base_alive["rewrite"]
            if alive is not None:
                hits = hits[alive[hits]]
            if hits.size:
                deleted.append(base_h[hits])
                d.kill_base("rewrite", base_h.shape[0], hits)
        rlog = d.log("rewrite")
        if rlog.n:
            dhits = RowLocator.build(rlog.rows).find(q)
            if dhits.size:
                dhits = dhits[rlog.alive[dhits]]
                if dhits.size:
                    deleted.append(rlog.rows[dhits])
                    rlog.tombstone(dhits)
        if not deleted:
            return np.zeros((0, 3), dtype=np.int32)
        return np.concatenate(deleted)

    def kill_derived_mentions(self, inst: np.ndarray) -> None:
        """Tombstone every derived row mentioning an affected instance.

        The instance-keyed SPO/OSP lookup touches only the hit runs, so
        this is O(k log N + hits) in the base size, not an O(N) scan.
        """
        d = self.delta
        for mode in ("litemat", "full"):
            idx = self._base_index(mode)
            d.kill_base(mode, idx.n, mention_rows(idx, inst))
            log = d.log(mode)
            if log.n:
                log.tombstone(mentions_mask(log.rows, inst))

    def live_raw_mentions(self, inst: np.ndarray) -> np.ndarray:
        """Live raw triples mentioning any affected instance (s or o).

        The re-derivation frontier of a delete: materializing these rows
        and keeping the derived rows that mention an affected instance is
        an exact repair of the derived stores.
        """
        d = self.delta
        base_h = self._base_index("rewrite")._h
        raw_alive = d.base_alive["rewrite"]
        raw_rows = mention_rows(self._base_index("rewrite"), inst)
        if raw_alive is not None:
            raw_rows = raw_rows[raw_alive[raw_rows]]
        parts = [base_h[raw_rows]]
        rlog = d.log("rewrite")
        if rlog.n:
            parts.append(rlog.rows[mentions_mask(rlog.rows, inst) & rlog.alive])
        return np.concatenate(parts)

    def delete(self, raw, auto_compact: bool = True) -> dict:
        """Remove raw triples (all copies) and repair the derived stores.

        Tombstones the raw rows, then re-derives every *affected instance*
        (endpoints of the deleted triples) from its remaining live triples:
        derived rows only ever mention their source triple's instances, so
        tombstoning rows that mention an affected instance and re-deriving
        from the live triples that mention one is an exact repair — no
        support counting, no full re-materialization.
        """
        s_fp, p_fp, o_fp, _ = _raw_columns(raw)
        if s_fp.shape[0] == 0:
            return dict(n_deleted=0)
        with self.write_lock:
            # the repair below tombstones + re-appends derived delta rows,
            # so any lazily queued materialization must land first
            self._flush_mat("litemat", "full")
            dyn = self._dynamic()
            ids = np.stack([dyn.lookup(s_fp), dyn.lookup(p_fp),
                            dyn.lookup(o_fp)], axis=1)
            q = ids[(ids >= 0).all(axis=1)]  # unknown-term triples: absent

            deleted = self.kill_raw_rows(q)
            if deleted.shape[0] == 0:
                return dict(n_deleted=0)
            inst = affected_instances(deleted, self.kb.tbox.instance_base)
            self.kill_derived_mentions(inst)

            # re-derive the affected instances from their live raw triples
            frontier = self.live_raw_mentions(inst)
            derived = run_concurrently([
                partial(materialize_delta_mode, frontier, self.dtb, mode)
                for mode in ("litemat", "full")])
            for mode, rows in zip(("litemat", "full"), derived):
                self.append_derived(mode, rows[mentions_mask(rows, inst)])
            self._bump()
            stats = dict(
                n_deleted=int(deleted.shape[0]),
                n_affected_instances=int(inst.shape[0]),
                delta_ratio=round(self.delta_ratio, 4),
                version=self.version,
            )
            if auto_compact and self.delta_ratio > self.compact_threshold:
                stats["compacted"] = self.compact()
            return stats

    def compact(self, device: bool | None = None) -> dict:
        """Fold the delta overlay into fresh base stores (sorted merges).

        Each store's base POS run interleaves with its delta POS run in one
        merge pass (tombstones dropped on the way); the merged run doubles
        as the new base array, so the rebuilt StoreIndex starts with its POS
        permutation already materialized (the other permutations re-sort
        lazily on first use).  Dictionary growth needs no work: new terms
        were absorbed into ``kb.tables`` at insert time.

        ``device`` selects the merge implementation: the device merge
        (``ops.merge_gather``) over the resident device buffers
        (bit-identical to the host merge; default on TPU backends) or the host searchsorted interleave
        (default elsewhere, where 'device' arrays live in host RAM anyway).
        """
        with self.write_lock:
            if ((self._delta is None or self._delta.empty)
                    and not self._pending_raw):
                return dict(compacted=False)
            with obs_trace.span("engine.compact"):
                self._flush_mat("litemat", "full")
                if device is None:
                    device = jax.default_backend() == "tpu"
                sizes = {}
                views = [self.view(mode) for mode in MODES]
                merged = run_concurrently([  # the stores' compiles overlap
                    partial(compact_view, v, device=device) for v in views])
                for mode, (dev, idx) in zip(MODES, merged):
                    if mode == "rewrite":
                        self.kb.spo = dev
                    elif mode == "litemat":
                        self.lite_spo = dev
                    else:
                        self.full_spo = dev
                    self._base_indexes[mode] = idx
                    sizes[mode] = int(dev.shape[0])
                self._delta = DeltaKB()
                self._raw_loc = None
                self._bump()
            return dict(compacted=True, version=self.version, **sizes)
