"""Snapshot-isolated MVCC reads over a live (Sharded)KnowledgeBase.

``KnowledgeBase.version`` was always the MVCC hook — every mutation bumps
it, and :class:`~repro.core.delta.StoreView` objects are already immutable
snapshots of one version (liveness masks copied at build, delta arrays
append-only).  What was missing is the *coordination*: a reader that grabs
views while a writer is mid-mutation can see a half-applied delete, and the
:class:`~repro.core.delta.DeviceStoreCache`'s donated tombstone scatters
can invalidate device buffers a long-running reader is still executing
against.  This module closes both holes:

  * Writers serialize through ``kb.write_lock`` (insert / delete / compact
    hold it for their whole mutate-and-bump critical section).
  * Readers **pin** a :class:`Snapshot` from the :class:`SnapshotRegistry`:
    an immutable bundle of per-mode StoreViews captured at a quiescent
    point (under the write lock), refcounted so compaction/retirement can
    never pull a pinned version out from under a running query.
  * Pinned views are flagged ``pinned=True``; the DeviceStoreCache then
    *leases* any resident buffer it hands them and copies (instead of
    donating) the base-alive mask on the next kill scatter — an O(base)
    copy paid at most once per (pin, delete) pair, zero cost when nothing
    is pinned (the donation fast path is untouched).
  * ``pin()`` degrades gracefully: when a writer holds the lock past
    ``lock_timeout_s`` (or the capture itself fails — e.g. an injected
    mid-flush crash), the reader is served the **last published** snapshot
    tagged ``stale=True`` instead of blocking or erroring.

Snapshots work for both the single-device :class:`KnowledgeBase` and the
multi-device :class:`~repro.core.shard.ShardedKB` (per-shard views, queries
run through per-shard engines + the ordinary cross-shard combine).  Query
plans compile into registry-level caches shared across snapshots, so
pinning is cheap: no recompilation, no buffer copies, just refcounts.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field

import numpy as np

from repro.core.query import QueryEngine
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.testing import faults
from repro.utils.parallel import run_concurrently


def _is_sharded(kb) -> bool:
    return hasattr(kb, "shards")


@dataclass
class Snapshot:
    """Immutable per-mode views of ONE published version, refcounted.

    ``views[mode]`` is a StoreView (single store) or a per-shard list
    (ShardedKB).  Engines lazily attach to the pinned views and share the
    registry's plan caches, so repeated pins of the same version — and
    fresh pins after small mutations — reuse every compiled executable.
    """

    version: int
    kb: object
    modes: tuple
    views: dict
    use_index: bool = True
    refs: int = 0
    _plan_caches: dict = field(default_factory=dict, repr=False)
    # PatternSig -> observed selectivity, shared across snapshots via the
    # registry so planner feedback survives version churn
    _selectivity: dict = field(default_factory=dict, repr=False)
    _engines: dict = field(default_factory=dict, repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    @property
    def sharded(self) -> bool:
        return _is_sharded(self.kb)

    def _check_mode(self, mode: str) -> str:
        mode = mode or self.modes[0]
        if mode not in self.views:
            raise KeyError(
                f"mode {mode!r} not captured by this snapshot (captured: "
                f"{tuple(self.views)}) — pass modes=(...) to the registry")
        return mode

    def _plan_cache(self, mode: str) -> dict:
        return self._plan_caches.setdefault((mode, self.use_index), {})

    def engine(self, mode: str = None) -> QueryEngine:
        """A QueryEngine bound to this snapshot's pinned view (single store)."""
        mode = self._check_mode(mode)
        if self.sharded:
            raise ValueError("sharded snapshots query per shard — use query()")
        with self._lock:
            eng = self._engines.get(mode)
            if eng is None:
                view = self.views[mode]
                eng = QueryEngine(
                    kb=self.kb.kb, spo=view.base_rows, mode=mode,
                    dtb=self.kb.dtb, use_index=self.use_index, view=view,
                    _exec_cache=self._plan_cache(mode),
                    observed_selectivity=self._selectivity)
                self._engines[mode] = eng
            return eng

    def _shard_engines(self, mode: str) -> list:
        with self._lock:
            engines = self._engines.get(mode)
            if engines is None:
                cache = self._plan_cache(mode)
                engines = [
                    QueryEngine(kb=K.kb, spo=v.base_rows, mode=mode,
                                dtb=self.kb.dtb, use_index=self.use_index,
                                view=v, _exec_cache=cache,
                                observed_selectivity=self._selectivity)
                    for K, v in zip(self.kb.shards, self.views[mode])]
                self._engines[mode] = engines
            return engines

    def query(self, patterns, select=None, mode: str = None):
        """Evaluate against the pinned version — never the live store."""
        mode = self._check_mode(mode)
        if self.sharded:
            return self._query_sharded(patterns, select, mode)
        return self.engine(mode).run(patterns, select=select)

    def _query_sharded(self, patterns, select, mode: str):
        """Per-shard dispatch over the pinned views + global combine.

        Snapshot reads always take the per-shard loop (the degradation
        target of the shard_map path as well): each shard's plan runs
        against that shard's pinned view, then the groups combine exactly
        like the live ShardedQueryEngine.
        """
        from repro.core.shard import _group_vars, combine_groups, plan_groups

        patterns = list(patterns)
        groups = plan_groups(patterns, mode, self.kb.tbox)
        engines = self._shard_engines(mode)
        views = self.views[mode]
        evaluated = []
        with obs_trace.span("shard.dispatch", path="loop",
                            n_groups=len(groups), n_shards=len(engines)):
            for g in groups:
                gpats = [patterns[i] for i in g]
                gvars = _group_vars(gpats)
                parts = []
                for i, eng in enumerate(engines):
                    if views[i].n == 0:
                        continue
                    faults.fire("shard.query_shard", shard=i)
                    with self.kb._device_ctx(i):
                        rows, _ = eng.run(gpats, select=gvars)
                    if rows.shape[0]:
                        parts.append(np.asarray(rows, dtype=np.int32))
                evaluated.append((gvars, parts))
        return combine_groups(evaluated, patterns, select)

    def query_batch(self, requests, mode: str = None):
        """Evaluate a batch of (patterns, select) requests at the pinned
        version with shared dispatches; returns per-request (rows, sel).

        Single store: straight to the engine's vmapped
        :meth:`~repro.core.query.QueryEngine.run_batch`.  Sharded: every
        member is decomposed into its pattern groups (exactly like
        :meth:`_query_sharded`) and ALL members' groups ride one
        ``run_batch`` per shard — same-signature groups from different
        requests coalesce inside the engine — before each member combines
        its own groups.
        """
        mode = self._check_mode(mode)
        if not self.sharded:
            return self.engine(mode).run_batch(requests)
        return self._query_batch_sharded(requests, mode)

    def _query_batch_sharded(self, requests, mode: str):
        from repro.core.shard import _group_vars, combine_groups, plan_groups

        engines = self._shard_engines(mode)
        views = self.views[mode]
        members = []     # (patterns, select, [gvars...], [flat idx...])
        shard_reqs = []  # flattened (group patterns, group vars)
        for pats, select in requests:
            pats = list(pats)
            groups = plan_groups(pats, mode, self.kb.tbox)
            metas, idxs = [], []
            for g in groups:
                gpats = [pats[i] for i in g]
                gvars = _group_vars(gpats)
                idxs.append(len(shard_reqs))
                shard_reqs.append((gpats, gvars))
                metas.append(gvars)
            members.append((pats, select, metas, idxs))
        parts_by_flat = [[] for _ in shard_reqs]
        with obs_trace.span("shard.dispatch", path="batch",
                            n_groups=len(shard_reqs),
                            n_shards=len(engines)):
            for i, eng in enumerate(engines):
                if views[i].n == 0:
                    continue
                faults.fire("shard.query_shard", shard=i)
                with self.kb._device_ctx(i):
                    res = eng.run_batch(shard_reqs)
                for f, (rows, _) in enumerate(res):
                    if rows.shape[0]:
                        parts_by_flat[f].append(
                            np.asarray(rows, dtype=np.int32))
        out = []
        for pats, select, metas, idxs in members:
            evaluated = [(metas[j], parts_by_flat[f])
                         for j, f in enumerate(idxs)]
            out.append(combine_groups(evaluated, pats, select))
        return out

    def answers(self, patterns, select=None, mode: str = None) -> set:
        rows, _ = self.query(patterns, select=select, mode=mode)
        with obs_trace.span("serving.answers", rows=len(rows)):
            return {tuple(r) for r in rows.tolist()}

    def device_buffers(self) -> list:
        """Device buffers this snapshot's pinned views keep alive.

        Reported under the ``snapshot`` component: after a compaction the
        live store swaps to fresh arrays, and whatever a pinned version
        still references — superseded bases, leased liveness masks — is
        memory *retained by MVCC*, exactly what an operator needs to see
        attributed separately.  Buffer ids let the ledger dedupe against
        the live store's own records, so only genuinely retained bytes
        surface here when the live KB registers first.
        """
        out = []
        for views in self.views.values():
            for v in views if isinstance(views, list) else (views,):
                for _comp, buf_id, nbytes in v.device_buffers():
                    out.append(("snapshot", buf_id, nbytes))
        return out

    def store_rows(self, mode: str = None) -> np.ndarray:
        """Live rows at the pinned version (host; shards concatenated)."""
        mode = self._check_mode(mode)
        if self.sharded:
            return np.concatenate(
                [np.asarray(v.live_rows()) for v in self.views[mode]])
        return np.asarray(self.views[mode].live_rows())


class Pin:
    """One reader's lease on a snapshot: context-managed refcount + tag.

    ``stale=True`` marks a degraded pin — the store had moved (or the
    writer held the lock) and the reader was served the last *published*
    version instead of the newest one.  Queries still answer exactly at
    ``version``; the tag just tells the client which version that is.
    """

    def __init__(self, registry: "SnapshotRegistry", snapshot: Snapshot,
                 stale: bool):
        self._registry = registry
        self.snapshot = snapshot
        self.stale = stale
        self._released = False

    @property
    def version(self) -> int:
        return self.snapshot.version

    def query(self, patterns, select=None, mode: str = None):
        return self.snapshot.query(patterns, select=select, mode=mode)

    def query_batch(self, requests, mode: str = None):
        return self.snapshot.query_batch(requests, mode=mode)

    def answers(self, patterns, select=None, mode: str = None) -> set:
        return self.snapshot.answers(patterns, select=select, mode=mode)

    def store_rows(self, mode: str = None):
        return self.snapshot.store_rows(mode)

    def release(self) -> None:
        if not self._released:
            self._released = True
            self._registry._release(self.snapshot)

    def __enter__(self) -> "Pin":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class SnapshotRegistry:
    """Publish/pin/retire lifecycle for MVCC snapshots of one store.

    * ``publish()`` captures the current version under the write lock and
      makes it the registry's serving snapshot.
    * ``pin()`` hands a reader a refcounted :class:`Pin`.  Fast path: the
      published snapshot already matches ``kb.version``.  Slow path: grab
      the write lock (bounded by ``lock_timeout_s``) and capture a fresh
      one.  Degraded path: the lock is contended or the capture failed —
      serve the last published snapshot tagged stale (never block a
      reader on a writer).
    * ``retire()`` drops refcount-zero snapshots that are no longer
      published; pinned versions survive any number of writes and
      compactions (their views keep the superseded base arrays alive).
    """

    def __init__(self, kb, modes=("litemat",), use_index: bool = True,
                 lock_timeout_s: float = 0.2,
                 metrics: MetricsRegistry | None = None):
        self.kb = kb
        self.modes = tuple(modes)
        self.use_index = use_index
        self.lock_timeout_s = lock_timeout_s
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._lock = threading.Lock()
        self._snaps: dict = {}  # version -> Snapshot
        self._published: Snapshot | None = None
        self._plan_caches: dict = {}  # shared across snapshots
        self._selectivity: dict = {}  # PatternSig -> observed, ditto

    @property
    def stats(self) -> dict:
        """Counter dict view over the registry (kept PR-6-shaped)."""
        m = self.metrics
        return {
            "publishes": m.counter_value("snapshot/publishes"),
            "pins": m.counter_value("snapshot/pins"),
            "stale_pins": m.counter_value("snapshot/stale_pins"),
            "fresh_captures": m.counter_value("snapshot/fresh_captures"),
            "retired": m.counter_value("snapshot/retired"),
            "capture_failures": m.counter_value("snapshot/capture_failures"),
        }

    def _refresh_gauges_locked(self) -> None:
        """Version/refcount gauges; caller holds self._lock."""
        self.metrics.gauge("snapshot/pinned_refs").set(
            sum(s.refs for s in self._snaps.values()))

    # -- capture / publish ---------------------------------------------------
    def _capture(self) -> dict:
        """Build per-mode views at the current version (write lock held)."""
        kb = self.kb
        views: dict = {}
        for mode in self.modes:
            if _is_sharded(kb):
                if mode in ("litemat", "full"):
                    kb._flush(mode)
                vs = []
                for i, K in enumerate(kb.shards):
                    with kb._device_ctx(i):
                        vs.append(K.view(mode))
                for v in vs:
                    v.pinned = True
                views[mode] = vs
            else:
                v = kb.view(mode)
                v.pinned = True
                views[mode] = v
        return views

    def _publish_locked(self) -> Snapshot:
        """Capture-or-reuse the snapshot of kb.version (write lock held)."""
        v = self.kb.version
        with self._lock:
            snap = self._snaps.get(v)
        if snap is None:
            with obs_trace.span("snapshot.capture", version=v):
                faults.fire("snapshot.publish", version=v)
                views = self._capture()
            snap = Snapshot(version=v, kb=self.kb, modes=self.modes,
                            views=views, use_index=self.use_index,
                            _plan_caches=self._plan_caches,
                            _selectivity=self._selectivity)
            with self._lock:
                # another thread may have captured v concurrently; keep the
                # first registered one so refcounts aggregate correctly
                snap = self._snaps.setdefault(v, snap)
        with self._lock:
            self._published = snap
            self._refresh_gauges_locked()
        self.metrics.counter("snapshot/publishes").inc()
        self.retire()
        return snap

    def publish(self) -> Snapshot:
        """Capture the current version as the serving snapshot."""
        with self.kb.write_lock:
            return self._publish_locked()

    @property
    def published(self) -> Snapshot | None:
        with self._lock:
            return self._published

    # -- pin / release -------------------------------------------------------
    def pin(self, lock_timeout_s: float | None = None) -> Pin:
        """Pin a snapshot for reading; degrade to the last published one
        (stale tag) rather than blocking on a busy writer."""
        m = self.metrics
        m.counter("snapshot/pins").inc()
        with self._lock:
            snap = self._published
            if snap is not None and snap.version == self.kb.version:
                snap.refs += 1
                self._refresh_gauges_locked()
                m.counter("snapshot/pin_path", path="fast").inc()
                return Pin(self, snap, stale=False)

        # the store moved past the published snapshot: try a fresh capture
        timeout = (self.lock_timeout_s if lock_timeout_s is None
                   else lock_timeout_s)
        got = self.kb.write_lock.acquire(timeout=timeout)
        if got:
            try:
                snap = self._publish_locked()
            except Exception:
                m.counter("snapshot/capture_failures").inc()
                obs_trace.event("capture_failed")
                snap = None
            finally:
                self.kb.write_lock.release()
            if snap is not None:
                m.counter("snapshot/fresh_captures").inc()
                m.counter("snapshot/pin_path", path="fresh").inc()
                with self._lock:
                    snap.refs += 1
                    self._refresh_gauges_locked()
                    return Pin(self, snap, stale=False)

        # degraded: writer holds the flush lock (or the capture crashed) —
        # serve the last published version with a staleness tag
        with self._lock:
            snap = self._published
            if snap is not None:
                m.counter("snapshot/stale_pins").inc()
                m.counter("snapshot/pin_path", path="stale").inc()
                obs_trace.event("stale_pin", version=snap.version)
                snap.refs += 1
                self._refresh_gauges_locked()
                return Pin(self, snap, stale=True)
        if got is False and snap is None:
            # nothing ever published: block once for the first capture
            with self.kb.write_lock:
                snap = self._publish_locked()
            m.counter("snapshot/pin_path", path="first").inc()
            with self._lock:
                snap.refs += 1
                self._refresh_gauges_locked()
                return Pin(self, snap, stale=False)
        raise RuntimeError("snapshot capture failed and nothing is published")

    def pin_version(self, version: int) -> Pin | None:
        """Re-pin a SPECIFIC live version — the cursor-continuation path.

        Pagination needs page K+1 to read the exact rows page K saw, so a
        cursor re-pins its version by number.  Returns None when that
        version has been retired (no reader kept it alive between pages);
        the caller degrades to a fresh pin + ``stale`` cursor rather than
        erroring.  The Pin is tagged stale when the store has moved past
        the cursor's version — answers are still exact at that version.
        """
        m = self.metrics
        with self._lock:
            snap = self._snaps.get(version)
            if snap is None:
                m.counter("snapshot/pin_path", path="cursor_miss").inc()
                return None
            m.counter("snapshot/pins").inc()
            m.counter("snapshot/pin_path", path="cursor").inc()
            snap.refs += 1
            self._refresh_gauges_locked()
            return Pin(self, snap, stale=snap.version != self.kb.version)

    def _release(self, snap: Snapshot) -> None:
        with self._lock:
            snap.refs -= 1
            self._refresh_gauges_locked()
        self.retire()

    # -- retirement ----------------------------------------------------------
    def retire(self) -> int:
        """Drop refcount-zero snapshots that are no longer published.

        Two-phase on purpose: victims picked under the lock, then the
        ``snapshot.retire`` fault site fires (the race window a concurrent
        pin could hit), then each victim is re-checked under the lock
        before removal — a pin that raced in keeps its snapshot.
        """
        with self._lock:
            victims = [v for v, s in self._snaps.items()
                       if s.refs == 0 and s is not self._published]
        if not victims:
            return 0
        faults.fire("snapshot.retire", versions=tuple(victims))
        dropped = 0
        with self._lock:
            for v in victims:
                s = self._snaps.get(v)
                if s is not None and s.refs == 0 and s is not self._published:
                    del self._snaps[v]
                    dropped += 1
            self._refresh_gauges_locked()
        if dropped:
            self.metrics.counter("snapshot/retired").inc(dropped)
        return dropped

    def device_buffers(self) -> list:
        """Ledger feed: buffers retained by live snapshot versions.

        Deduped across versions here (two snapshots of nearby versions
        share almost every array); deduped against the live store by the
        ledger's global id pass.  Also publishes per-version
        ``snapshot/retained_bytes{version=}`` gauges into this registry's
        metrics — the "leased/pinned buffer bytes per version" series —
        zeroing versions that retired since the last walk.  Pull-based:
        runs only when the ledger samples, never on the pin fast path.
        """
        with self._lock:
            snaps = sorted(self._snaps.items())
        out = []
        seen: set = set()
        published: set = set()
        for version, snap in snaps:
            retained = 0
            for comp, buf_id, nbytes in snap.device_buffers():
                if buf_id in seen:
                    continue
                seen.add(buf_id)
                out.append((comp, buf_id, nbytes))
                retained += int(nbytes)
            self.metrics.gauge("snapshot/retained_bytes",
                               version=version).set(retained)
            published.add(version)
        stale = getattr(self, "_bytes_versions", set()) - published
        for version in stale:
            self.metrics.gauge("snapshot/retained_bytes",
                               version=version).set(0)
        self._bytes_versions = published
        return out

    def live_versions(self) -> list:
        with self._lock:
            return sorted(self._snaps)

    def pinned_versions(self) -> list:
        with self._lock:
            return sorted(v for v, s in self._snaps.items() if s.refs > 0)

    def prewarm(self, queries=None, modes=None, selects=None) -> None:
        """Compile the plan caches once so serving pays no cold starts.

        On a single store every (mode, query) executable compiles
        concurrently.  ``selects`` gives each query's projection, as the
        requests that follow will ask for it (default: all its variables).
        """
        from repro.core.engine import PAPER_QUERIES

        queries = (list(queries) if queries is not None
                   else list(PAPER_QUERIES.values()))
        selects = selects if selects is not None else [None] * len(queries)
        with self.pin() as pin:
            if pin.snapshot.sharded:
                for mode in (modes or self.modes):
                    for q, sel in zip(queries, selects):
                        pin.query(q, select=sel, mode=mode)
                return
            run_concurrently([
                c for mode in (modes or self.modes)
                for c in pin.snapshot.engine(mode).prewarm_calls(
                    queries, selects=selects)])


__all__ = ["Snapshot", "SnapshotRegistry", "Pin"]
