"""Delta overlay: mutable state layered over immutable base triple stores.

LiteMat's interval encoding reserves unused local bits in every concept and
property id precisely so the KB can grow without re-encoding — this module
supplies the storage half of that promise.  A ``KnowledgeBase`` keeps its
base stores (raw / lite-materialized / fully-materialized) immutable and
routes every mutation through a :class:`DeltaKB`:

  * inserts append *encoded* rows to per-store :class:`DeltaLog` s
    (append-only, like an LSM memtable),
  * deletes flip per-row ``alive`` bits — tombstones — on both the base
    stores and the delta logs; nothing is ever moved until compaction.

Queries see the union through a :class:`StoreView`: host-side range lookups
run against the base :class:`StoreIndex` *and* a small delta index, and the
device work gathers from a *virtual* ``[base | delta]`` concatenation —
``StoreView.dev(key)`` hands the executor the base array and a
power-of-two-capacity delta bucket as SEPARATE device arrays, addressed in
combined coordinates (delta rows offset by the base row count).  Because
the base array is never re-concatenated, the device work of refreshing a
view after a mutation is O(delta), not O(base):

  * :class:`DeviceStoreCache` (one per store, owned by the KnowledgeBase,
    surviving version bumps) keeps each key's delta bucket resident and
    ``lax.dynamic_update_slice`` s only the appended tail (scan order) or
    re-uploads the O(delta) bucket (permutation orders, whose sort
    interleaves on every append),
  * base tombstones are applied as point scatters of the per-version kill
    events — O(#killed), never an O(base) mask re-upload,
  * buckets are powers of two, so executables compiled for one delta
    length serve every length up to the bucket, and the buffers themselves
    are reallocated only when a bucket boundary is crossed.

``compact()`` (driven by core/engine.py) folds a delta into its base with
one sorted-merge pass per materialized permutation.  The device path runs
the device merge (``ops.merge_gather``) over the resident buffers and drops tombstones with the stream-compaction kernel, so the
merged store is assembled on the accelerator; the host only pulls the
final array once to mirror it into the new StoreIndex's search keys.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.index import (
    INVALID, PERMUTATIONS, StoreIndex, merge_sorted, pad_rows as _pad_rows,
    pow2_bucket as _pow2,
)
from repro.kernels import ops
from repro.obs.metrics import REGISTRY

MODES = ("rewrite", "litemat", "full")  # raw / lite / full store names


@dataclass
class DeltaLog:
    """Append-only encoded triple log with a tombstone (``alive``) mask."""

    rows: np.ndarray = field(
        default_factory=lambda: np.zeros((0, 3), dtype=np.int32))
    alive: np.ndarray = field(default_factory=lambda: np.zeros(0, dtype=bool))
    tombstone_mut: int = 0  # bumps whenever alive bits flip (device resync)

    @property
    def n(self) -> int:
        return int(self.rows.shape[0])

    @property
    def n_live(self) -> int:
        return int(self.alive.sum())

    def append(self, rows: np.ndarray) -> None:
        rows = np.asarray(rows, dtype=np.int32).reshape(-1, 3)
        self.rows = np.concatenate([self.rows, rows])
        self.alive = np.concatenate(
            [self.alive, np.ones(rows.shape[0], dtype=bool)])

    def tombstone(self, mask_or_idx) -> None:
        """Kill log rows by bool mask or index array.

        The mut counter bumps only when a bit actually flips — a no-op
        tombstone pass must not invalidate resident device buckets (the
        counter is what DeviceStoreCache keys its O(cap) re-uploads on).
        """
        sel = self.alive[mask_or_idx]
        if sel.size == 0 or not sel.any():
            return
        self.alive[mask_or_idx] = False
        self.tombstone_mut += 1

    def live_rows(self) -> np.ndarray:
        return self.rows[self.alive]


@dataclass
class DeltaKB:
    """Mutable overlay for one KnowledgeBase: per-store logs + base tombstones.

    ``base_alive[mode]`` stays ``None`` (meaning all-alive) until the first
    delete touches that store, so insert-only workloads never materialize or
    ship O(base) masks.  ``kills[mode]`` records each delete's newly-killed
    base row indices (original store coordinates) so device caches can apply
    tombstones as point scatters instead of re-uploading O(base) masks.
    """

    logs: dict = field(default_factory=lambda: {m: DeltaLog() for m in MODES})
    base_alive: dict = field(
        default_factory=lambda: {m: None for m in MODES})
    kills: dict = field(default_factory=lambda: {m: [] for m in MODES})
    n_new_terms: int = 0

    def log(self, mode: str) -> DeltaLog:
        return self.logs[mode]

    def kill_base(self, mode: str, base_n: int, row_idx: np.ndarray) -> int:
        """Tombstone base rows by index; returns how many were newly killed."""
        row_idx = np.asarray(row_idx, dtype=np.int64).reshape(-1)
        if row_idx.size == 0:
            return 0  # never materialize the O(base) mask for a no-op
        if self.base_alive[mode] is None:
            self.base_alive[mode] = np.ones(base_n, dtype=bool)
        mask = self.base_alive[mode]
        newly = row_idx[mask[row_idx]]
        if newly.size:
            mask[newly] = False
            self.kills[mode].append(newly)
        return int(newly.size)

    def n_rows(self, mode: str) -> int:
        return self.logs[mode].n

    @property
    def empty(self) -> bool:
        return (
            all(log.n == 0 for log in self.logs.values())
            and all(a is None for a in self.base_alive.values())
        )

    def ratio(self, base_sizes: dict, extra_rows: int = 0) -> float:
        """Overlay pressure: (delta rows + base tombstones) / base rows.

        ``extra_rows`` accounts for insert batches whose lite/full
        materialization is still pending (lazy per-mode derivation).
        """
        num, den = extra_rows, 0
        for m in MODES:
            n_base = int(base_sizes.get(m, 0))
            den += n_base
            num += self.logs[m].n
            if self.base_alive[m] is not None:
                num += n_base - int(self.base_alive[m].sum())
        return num / max(den, 1)


# ---------------------------------------------------------------------------
# Device-resident [base | delta-bucket] buffers
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["base", "base_alive", "delta", "delta_alive"],
    meta_fields=[],
)
@dataclass
class DevStore:
    """One key's device arrays, addressed in combined [base | delta] coords.

    A registered pytree: executables take DevStores as traced arguments, so
    swapping in a refreshed delta bucket of the same shape reuses the
    compiled plan.  ``delta``/``delta_alive`` are ``None`` for delta-free
    views — the pytree structure then differs, so static stores compile
    single-source plans with zero overlay overhead, and the two-source
    plan is traced (once per bucket) only while a delta actually exists.
    """

    base: jnp.ndarray  # [Nb, 3] (or the scan-order store itself)
    base_alive: jnp.ndarray  # bool[Nb]
    delta: jnp.ndarray | None  # [Dcap, 3], INVALID-padded; None = no delta
    delta_alive: jnp.ndarray | None  # bool[Dcap]


def _pad_alive(alive: np.ndarray, cap: int) -> np.ndarray:
    pad = cap - alive.shape[0]
    if pad <= 0:
        return alive
    return np.concatenate([alive, np.zeros(pad, dtype=bool)])


def _delta_host(view: "StoreView", key: str):
    """(rows, alive) of the delta in ``key`` order — pure host, no uploads."""
    if key == "scan":
        return view.delta_h, view.delta_alive_h
    p = view.delta_index.perm(key)
    return view.delta_index._h[p.perm], view.delta_alive_h[p.perm]


@dataclass
class _DevState:
    """Cache entry: one (store, key) pair's resident buffers + provenance."""

    base_token: int
    base_alive: jnp.ndarray
    n_kills: int
    delta: jnp.ndarray
    delta_alive: jnp.ndarray
    cap: int
    delta_len: int
    tombstone_mut: int
    owns_alive: bool = False  # True once base_alive is a private buffer
    leased: bool = False  # True while a pinned snapshot may still hold
    # this base_alive buffer: the next kill batch must copy-then-donate
    # instead of donating the leased buffer out from under the snapshot


@partial(jax.jit, donate_argnums=(0,))
def _kill_scatter(alive, idx):
    """Tombstone point scatter with the alive buffer DONATED.

    Donation lets XLA flip the bits IN PLACE instead of realizing the
    ``.at[].set`` as an O(base) copy-then-scatter — a delete batch then
    costs O(#killed) device work AND zero base-sized allocations.  ``idx``
    is padded to a power-of-two bucket with out-of-range indices (dropped
    by the scatter) so kill batches of any size share a few executables.
    """
    return alive.at[idx].set(False, mode="drop")


def _pad_kill_idx(idx: np.ndarray, n: int) -> jnp.ndarray:
    """Kill indices -> pow2-padded int32 device array (pad rows dropped)."""
    cap = _pow2(idx.shape[0])
    pad = np.full(cap - idx.shape[0], n, dtype=np.int64)
    return jnp.asarray(np.concatenate([idx, pad]).astype(np.int32))


class DeviceStoreCache:
    """Per-store persistent device buffers, surviving KnowledgeBase versions.

    ``sync(view, key)`` brings the key's buffers up to the view's state with
    work *independent of the base size*: delta buckets are updated in place
    (appended tail for scan order, O(cap) re-upload for permutation orders)
    and base tombstones are applied as point scatters of the recorded kill
    events.  ``stats`` counts every host->device transfer in row units so
    tests/benchmarks can pin the O(delta) contract.
    """

    def __init__(self):
        self._states: dict = {}
        self._ones: dict = {}  # (token, n) -> shared all-alive mask
        self._lock = threading.RLock()  # sync() is reader-reentrant
        self.stats = {
            "base_rebuilds": 0,  # fresh states (new base / first touch)
            "delta_allocs": 0,  # delta bucket (re)allocations
            "upload_delta_rows": 0,  # delta rows shipped host->device
            "upload_alive_rows": 0,  # delta liveness bits shipped
            "upload_base_alive_rows": 0,  # full base masks shipped (fresh only)
            "kill_scatter_rows": 0,  # base tombstones applied as scatters
            "alive_privatize_rows": 0,  # one-time shared-mask copies (first
            # delete against a key whose resident mask is the SHARED
            # all-alive buffer; donation needs a private one)
            "lease_copy_rows": 0,  # copies forced by a pinned snapshot
            # leasing the resident mask (donation would invalidate it)
            "stale_view_builds": 0,  # one-off builds for out-of-date views
        }

    def _stat(self, key: str, n: int = 1) -> None:
        """Bump the local dict; row-unit upload counters also feed
        ``device/transfer_bytes`` in the process registry (12 B per
        [s,p,o] int32 row, 1 B per liveness bit) so the observability
        layer sees host->device traffic in one unit.
        """
        self.stats[key] += n
        if key == "upload_delta_rows":
            REGISTRY.counter("device/transfer_bytes",
                             src="store_cache").inc(n * 12)
        elif key in ("upload_alive_rows", "upload_base_alive_rows"):
            REGISTRY.counter("device/transfer_bytes",
                             src="store_cache").inc(n)

    def _all_alive(self, token: int, n: int) -> jnp.ndarray:
        key = (token, n)
        if key not in self._ones:
            # evict masks of superseded bases: without this, every
            # compaction (new token) would pin another O(base) device
            # array here for the cache's lifetime
            self._ones = {k: v for k, v in self._ones.items()
                          if k[0] == token}
            self._ones[key] = jnp.ones(n, dtype=bool)
        return self._ones[key]

    def _upload_delta(self, view: "StoreView", key: str, cap: int):
        if not view.has_delta:
            return None, None  # delta-free: single-source executables
        rows, alive = _delta_host(view, key)
        self._stat("upload_delta_rows", cap)
        self._stat("upload_alive_rows", cap)
        self._stat("delta_allocs")
        return (jnp.asarray(_pad_rows(rows, cap)),
                jnp.asarray(_pad_alive(alive, cap)))

    def _base_arrays(self, view: "StoreView", key: str):
        if key == "scan":
            return view.base_rows
        return view.base_index.perm(key).rows

    def _fresh(self, view: "StoreView", key: str, cap: int) -> _DevState:
        self._stat("base_rebuilds")
        token = view.base_index.token
        if view.base_alive_h is None:
            base_alive = self._all_alive(token, view.base_n)
        else:
            alive_h = (view.base_alive_h if key == "scan"
                       else view.base_alive_h[view.base_index.perm(key).perm])
            self._stat("upload_base_alive_rows", view.base_n)
            # the kill scatter donates this mask, so it must be a buffer
            # XLA allocated: on the CPU backend ``jnp.asarray`` wraps a
            # 64-byte-aligned host array in place, and a donated foreign
            # buffer is copied instead of updated in place
            base_alive = jnp.asarray(alive_h).copy()
        delta, dalive = self._upload_delta(view, key, cap)
        return _DevState(
            base_token=token, base_alive=base_alive,
            n_kills=len(view.kills), delta=delta, delta_alive=dalive,
            cap=cap if delta is not None else 0, delta_len=view.delta_n,
            tombstone_mut=view.delta_mut,
            owns_alive=view.base_alive_h is not None,
        )

    def sync(self, view: "StoreView", key: str) -> DevStore:
        # one writer xor many readers reach here concurrently only through
        # pinned snapshots; the lock makes resident-state updates atomic so
        # a reader can never observe a half-applied delta splice
        with self._lock:
            return self._sync_locked(view, key)

    def _sync_locked(self, view: "StoreView", key: str) -> DevStore:
        base = self._base_arrays(view, key)
        token = view.base_index.token
        cap = _pow2(view.delta_n) if view.has_delta else 0
        st = self._states.get(key)

        if st is not None and (
                token < st.base_token  # tokens are monotonic: older base
                or (st.base_token == token and (
                    view.delta_n < st.delta_len
                    or len(view.kills) < st.n_kills
                    or view.delta_mut < st.tombstone_mut))):
            # a view older than the resident state (held across later
            # mutations or a compaction): serve it a one-off build, never
            # rewind the cache — rewinding would make alternating
            # old-snapshot/live queries thrash O(base) rebuilds
            self._stat("stale_view_builds")
            return _one_off_dev(view, key, base)

        if st is None or st.base_token != token:
            st = self._fresh(view, key, cap)
            self._states[key] = st
        else:
            if cap != st.cap:
                # bucket boundary crossed (or first delta after an empty
                # state): reallocate the delta bucket (O(new cap)); the
                # base array is untouched either way
                st.delta, st.delta_alive = self._upload_delta(view, key, cap)
                st.cap, st.delta_len = cap, view.delta_n
                st.tombstone_mut = view.delta_mut
            elif st.delta is not None and (
                    view.delta_n != st.delta_len
                    or view.delta_mut != st.tombstone_mut):
                grew = view.delta_n - st.delta_len
                if grew > 0:
                    if key == "scan":
                        # append order: splice ONLY the appended tail
                        tail = np.asarray(view.delta_h[st.delta_len:],
                                          dtype=np.int32)
                        st.delta = lax.dynamic_update_slice(
                            st.delta, jnp.asarray(tail), (st.delta_len, 0))
                        self._stat("upload_delta_rows", grew)
                    else:
                        rows, _ = _delta_host(view, key)
                        st.delta = jnp.asarray(_pad_rows(rows, cap))
                        self._stat("upload_delta_rows", cap)
                # grew == 0 means a tombstone-only change: the log is
                # append-only, so the resident ROW buckets are already
                # correct in every order — refresh just the alive bits
                _, alive = _delta_host(view, key)
                st.delta_alive = jnp.asarray(_pad_alive(alive, cap))
                self._stat("upload_alive_rows", cap)
                st.delta_len = view.delta_n
                st.tombstone_mut = view.delta_mut
            if len(view.kills) > st.n_kills:
                idx = np.concatenate(view.kills[st.n_kills:])
                if key != "scan":
                    idx = view.base_index.inv_perm(key)[idx]
                if not st.owns_alive or st.leased:
                    # resident mask is either the SHARED all-alive buffer or
                    # LEASED to a pinned snapshot: copy it once so the kill
                    # batch donates a private buffer — the snapshot (or the
                    # shared mask) keeps the original, and every later kill
                    # donates the copy back in place at zero extra cost
                    stat = ("lease_copy_rows" if st.owns_alive
                            else "alive_privatize_rows")
                    st.base_alive = jnp.array(st.base_alive)
                    st.owns_alive = True
                    st.leased = False
                    self._stat(stat, int(st.base_alive.shape[0]))
                st.base_alive = _kill_scatter(
                    st.base_alive,
                    _pad_kill_idx(idx, int(st.base_alive.shape[0])))
                self._stat("kill_scatter_rows", int(idx.shape[0]))
                st.n_kills = len(view.kills)

        if view.pinned:
            # a pinned snapshot now references the resident buffers: mark
            # the base mask leased so the next delete copies instead of
            # donating it out from under the snapshot's DevStore
            st.leased = True
        return DevStore(base=base, base_alive=st.base_alive,
                        delta=st.delta, delta_alive=st.delta_alive)

    def buffer_shapes(self, key: str):
        """(delta bucket shape, capacity) — test hook for the O(delta) pins."""
        st = self._states.get(key)
        if st is None:
            return None
        shape = (0, 3) if st.delta is None else tuple(st.delta.shape)
        return shape, st.cap

    def device_buffers(self) -> list:
        """Resident device buffers as (component, id, nbytes) records.

        The :class:`~repro.obs.ledger.ResourceLedger` feed: pow2 delta
        buckets under ``delta``, liveness masks (delta, privatized base,
        and the shared all-alive buffers) under ``alive``.  Ids let the
        ledger dedupe buffers shared across owners (e.g. a snapshot still
        leasing a resident mask).  Side-effect-free: walks existing
        state, never materializes anything.
        """
        out = []
        with self._lock:
            for st in self._states.values():
                if st.delta is not None:
                    out.append(("delta", id(st.delta), st.delta.nbytes))
                    out.append(("alive", id(st.delta_alive),
                                st.delta_alive.nbytes))
                if st.owns_alive:
                    out.append(("alive", id(st.base_alive),
                                st.base_alive.nbytes))
            for mask in self._ones.values():
                out.append(("alive", id(mask), mask.nbytes))
        return out


def _one_off_dev(view: "StoreView", key: str, base) -> DevStore:
    """Cacheless DevStore build (static views, stale snapshots, tests)."""
    if view.base_alive_h is None:
        base_alive = jnp.ones(view.base_n, dtype=bool)
    else:
        alive_h = (view.base_alive_h if key == "scan"
                   else view.base_alive_h[view.base_index.perm(key).perm])
        base_alive = jnp.asarray(alive_h)
    if not view.has_delta:
        delta = dalive = None
    else:
        cap = _pow2(view.delta_n)
        rows, alive = _delta_host(view, key)
        delta = jnp.asarray(_pad_rows(rows, cap))
        dalive = jnp.asarray(_pad_alive(alive, cap))
    return DevStore(base=base, base_alive=base_alive,
                    delta=delta, delta_alive=dalive)


# ---------------------------------------------------------------------------
# StoreView: what a QueryEngine executes against
# ---------------------------------------------------------------------------


@dataclass
class StoreView:
    """Union of an immutable base store and a (small) delta overlay.

    Presents the same range-lookup surface as StoreIndex, but every lookup
    returns a *list* of ranges in combined coordinates: base ranges first,
    then delta ranges offset by the base row count.  Device consumers call
    ``dev(key)`` for the matching :class:`DevStore` — base array plus a
    power-of-two delta bucket as separate device arrays (INVALID rows and
    ``alive=False`` padding), so executables compiled for one delta bucket
    serve every delta length up to it and a mutation never re-concatenates
    the base on device.
    """

    base_rows: jnp.ndarray  # device [Nb, 3] — the original store array
    base_h: np.ndarray  # host copy (shared with the base StoreIndex)
    base_alive_h: np.ndarray | None = None  # None = every base row live
    delta_h: np.ndarray | None = None  # host [M, 3] delta log rows
    delta_alive_h: np.ndarray | None = None  # bool[M]
    base_index: StoreIndex | None = None
    cache: DeviceStoreCache | None = None  # persistent device buffers
    kills: tuple = ()  # snapshot of DeltaKB.kills[mode] (original coords)
    delta_mut: int = 0  # DeltaLog.tombstone_mut at snapshot time
    pinned: bool = False  # held by a Snapshot: cache leases (never donates)
    # any resident buffer it hands this view — see DeviceStoreCache.sync
    _delta_index: StoreIndex | None = field(default=None, repr=False)
    _dev: dict = field(default_factory=dict, repr=False)

    @classmethod
    def static(cls, spo) -> "StoreView":
        """A view over a plain store: no delta, no tombstones."""
        return cls(base_rows=jnp.asarray(spo), base_h=np.asarray(spo))

    @classmethod
    def overlay(cls, base_rows, base_index: StoreIndex,
                log: DeltaLog, base_alive: np.ndarray | None,
                cache: DeviceStoreCache | None = None,
                kills: tuple = ()) -> "StoreView":
        # snapshot the liveness masks: deletes flip tombstone bits IN PLACE
        # on the DeltaKB arrays, and a view must stay a consistent snapshot
        # of its version even if it is held across later mutations (its
        # per-permutation device buffers materialize lazily).
        return cls(
            base_rows=base_rows,
            base_h=base_index._h,
            base_alive_h=None if base_alive is None else base_alive.copy(),
            delta_h=log.rows if log.n else None,
            delta_alive_h=log.alive.copy() if log.n else None,
            base_index=base_index,
            cache=cache,
            kills=tuple(kills),
            delta_mut=log.tombstone_mut,
        )

    def __post_init__(self):
        if self.base_index is None:
            self.base_index = StoreIndex(_h=self.base_h)

    # -- shape bookkeeping ---------------------------------------------------
    @property
    def base_n(self) -> int:
        return int(self.base_h.shape[0])

    @property
    def delta_n(self) -> int:
        return 0 if self.delta_h is None else int(self.delta_h.shape[0])

    @property
    def delta_cap(self) -> int:
        """Power-of-two bucket the delta side is padded to on device."""
        return _pow2(self.delta_n)

    @property
    def has_delta(self) -> bool:
        return self.delta_n > 0

    @property
    def n(self) -> int:
        """Total addressable rows (planning upper bound, tombstones included)."""
        return self.base_n + self.delta_n

    @property
    def n_live(self) -> int:
        live = self.base_n if self.base_alive_h is None else int(
            self.base_alive_h.sum())
        if self.delta_alive_h is not None:
            live += int(self.delta_alive_h.sum())
        return live

    def live_rows(self) -> np.ndarray:
        """Host compaction of the view: all live rows, base-then-delta order."""
        base = (self.base_h if self.base_alive_h is None
                else self.base_h[self.base_alive_h])
        if self.delta_h is None:
            return base
        return np.concatenate([base, self.delta_h[self.delta_alive_h]])

    @property
    def delta_index(self) -> StoreIndex:
        if self._delta_index is None:
            self._delta_index = StoreIndex.build(self.delta_h)
        return self._delta_index

    # -- device views --------------------------------------------------------
    def dev(self, key: str) -> DevStore:
        """Device arrays of one view key ('scan' or a permutation name).

        Routed through the owning store's :class:`DeviceStoreCache` when one
        is attached (the live KnowledgeBase path — O(delta) refresh);
        otherwise built once per view and memoized (static stores, tests).
        """
        if self.cache is not None:
            return self.cache.sync(self, key)
        if key not in self._dev:
            base = (self.base_rows if key == "scan"
                    else self.base_index.perm(key).rows)
            self._dev[key] = _one_off_dev(self, key, base)
        return self._dev[key]

    def warm_device(self, keys=("scan", "pos")):
        """Materialize device buffers for ``keys``; returns them (blocking).

        The benchmarkable unit of post-mutation warmup: everything a first
        query needs beyond cached executables.
        """
        import jax

        out = [self.dev(k) for k in keys]
        for ds in out:
            jax.block_until_ready([a for a in (ds.base, ds.base_alive,
                                               ds.delta, ds.delta_alive)
                                   if a is not None])
        return out

    def device_buffers(self) -> list:
        """Device buffers this view references — ledger feed records.

        Covers the base store array and any one-off :class:`DevStore`
        memos (static views, stale snapshots); cache-routed buffers are
        reported by the owning :class:`DeviceStoreCache` instead.  Ids
        dedupe the walk against other owners of the same arrays.
        """
        out = [("base", id(self.base_rows), self.base_rows.nbytes)]
        if self.base_index is not None:
            for p in self.base_index._perms.values():
                out.append(("base", id(p.rows), p.rows.nbytes))
        for ds in self._dev.values():
            out.append(("base", id(ds.base), ds.base.nbytes))
            out.append(("alive", id(ds.base_alive), ds.base_alive.nbytes))
            if ds.delta is not None:
                out.append(("delta", id(ds.delta), ds.delta.nbytes))
                out.append(("alive", id(ds.delta_alive),
                            ds.delta_alive.nbytes))
        return out

    @property
    def all_alive(self) -> bool:
        """True iff no tombstone exists anywhere in the view."""
        return (
            self.base_alive_h is None
            and (self.delta_alive_h is None or bool(self.delta_alive_h.all()))
        )

    # -- combined range lookups ---------------------------------------------
    def _combine(self, base_range, delta_range):
        out = [base_range]
        if self.has_delta:
            r0, r1 = delta_range
            out.append((self.base_n + r0, self.base_n + r1))
        return out

    def p_ranges(self, plo: int, phi: int):
        base = self.base_index.p_range(plo, phi)
        return self._combine(
            base, self.delta_index.p_range(plo, phi) if self.has_delta else None)

    def po_ranges(self, p_id: int, olo: int, ohi: int):
        return self._combine(
            self.base_index.po_range(p_id, olo, ohi),
            self.delta_index.po_range(p_id, olo, ohi) if self.has_delta else None)

    def ps_ranges(self, p_id: int, slo: int, shi: int):
        return self._combine(
            self.base_index.ps_range(p_id, slo, shi),
            self.delta_index.ps_range(p_id, slo, shi) if self.has_delta else None)

    def s_ranges(self, slo: int, shi: int):
        return self._combine(
            self.base_index.s_range(slo, shi),
            self.delta_index.s_range(slo, shi) if self.has_delta else None)

    def o_ranges(self, olo: int, ohi: int):
        return self._combine(
            self.base_index.o_range(olo, ohi),
            self.delta_index.o_range(olo, ohi) if self.has_delta else None)

    def distinct_p_ids(self, plo: int, phi: int, limit: int = 8):
        """Distinct predicate ids in [plo, phi) across base AND delta.

        None when either side is too mixed (past ``limit``) — the
        index-nested-loop planner then leaves the pattern on its
        slice/scan strategy.
        """
        base = self.base_index.distinct_p_ids(plo, phi, limit)
        if base is None:
            return None
        if not self.has_delta:
            return base
        extra = self.delta_index.distinct_p_ids(plo, phi, limit)
        if extra is None:
            return None
        out = sorted(set(base) | set(extra))
        return out if len(out) <= limit else None

    def single_p_run(self, plo: int, phi: int):
        """Unique predicate id inside [plo, phi) across base AND delta."""
        b0, b1 = self.base_index.p_range(plo, phi)
        pid = self.base_index.single_p_run(b0, b1)
        if not self.has_delta:
            return pid
        r0, r1 = self.delta_index.p_range(plo, phi)
        dpid = self.delta_index.single_p_run(r0, r1)
        if r1 <= r0:  # delta has no rows in the interval: base decides
            return pid
        if b1 <= b0:  # base empty: delta decides
            return dpid
        return pid if (pid is not None and pid == dpid) else None


# ---------------------------------------------------------------------------
# Compaction: fold a view into a fresh base store
# ---------------------------------------------------------------------------


def compact_view(view: StoreView, device: bool = False):
    """Merge a view's live rows -> (device rows, pre-sorted StoreIndex).

    The merged array is produced in POS order with one sorted-merge pass
    (base POS run ⋈ delta POS run), so the returned index gets its POS
    permutation — the one every predicate/type pattern hits — for free;
    tombstones are dropped during the merge.  The other permutations stay
    lazy in the new index and re-sort on first use.

    ``device=True`` runs the merge on the accelerator: ``ops.merge_gather``
    computes the interleave over the resident [base | delta]
    buffers, the stream-compaction kernel drops tombstones, and the merged
    store is materialized by device gathers — bit-identical to the host
    path (pinned by tests), with the host only pulling the finished array
    once to mirror it into the new index's search keys.
    """
    if device:
        return _compact_view_device(view)
    base_idx = view.base_index
    bp = base_idx.perm("pos")
    b_keep = (slice(None) if view.base_alive_h is None
              else view.base_alive_h[bp.perm])
    b_rows, b_key = np.asarray(bp.rows)[b_keep], bp.key[b_keep]
    if not view.has_delta:
        merged = b_rows
        idx = StoreIndex.from_sorted(merged, "pos")
        return idx.perm("pos").rows, idx
    dp = view.delta_index.perm("pos")
    d_keep = view.delta_alive_h[dp.perm]
    merged, _ = merge_sorted(
        b_rows, b_key, np.asarray(dp.rows)[d_keep], dp.key[d_keep])
    idx = StoreIndex.from_sorted(merged, "pos")
    return idx.perm("pos").rows, idx


def _compact_view_device(view: StoreView):
    """Device-side compaction over the resident POS buffers."""
    ds = view.dev("pos")
    if ds.delta is None:  # tombstone-only fold: no merge, just compact
        dk = jnp.zeros((0,), dtype=jnp.int32)
        gidx = ops.merge_gather(ds.base[:, 1], ds.base[:, 2], dk, dk)
        alive = ops.two_source_gather(ds.base_alive, None, gidx)
    else:
        # merge EVERYTHING (tombstones and bucket padding included: INVALID
        # keys sort last and are dead) then compact by liveness — a stable
        # merge followed by a stable filter equals the merge of the
        # filtered runs.
        gidx = ops.merge_gather(ds.base[:, 1], ds.base[:, 2],
                                ds.delta[:, 1], ds.delta[:, 2])
        alive = ops.two_source_gather(ds.base_alive, ds.delta_alive, gidx)
    n_live = view.n_live
    take, _, _ = ops.compact_indices(alive, _pow2(n_live))
    src = gidx[take]
    merged_dev = ops.two_source_gather(ds.base, ds.delta, src)[:n_live]
    merged_h = np.asarray(merged_dev)
    idx = StoreIndex.from_sorted(merged_h, "pos", dev_rows=merged_dev)
    return merged_dev, idx


__all__ = ["DeltaLog", "DeltaKB", "StoreView", "DevStore", "DeviceStoreCache",
           "compact_view", "MODES", "PERMUTATIONS"]
