"""Sharded multi-device stores: the ABox subject-hash partitioned.

LiteMat's headline claim is that the encoding is computed and served by a
scalable *parallel* algorithm; this module supplies the partitioned store
layer.  A :class:`ShardedKB` splits every ABox store across ``n_shards``
shards (one per device when the host has several) while replicating the
things that make RDFS inference shard-local:

Partitioning invariants
-----------------------
  * Every ABox row lives on ``shard_of(subject id)``: raw triples by their
    subject, *derived* rows by THEIR subject — range-derived type rows
    ``(o rdf:type C)`` migrate to ``shard(o)`` in the post-materialization
    exchange, so the subject-hash invariant holds for all three stores
    (rewrite / litemat / full).
  * The TBox (interval tables, DeviceTBox) and the term dictionary are
    REPLICATED: every interval containment test, MSC selection, and
    closure gather is shard-local; the dictionary grows through ONE shared
    :class:`DynamicDictionary` whose new-term chunks are absorbed into
    every shard's ``EncodedKB``.
  * Each shard is a full single-device :class:`KnowledgeBase` — its own
    POS/PSO/SPO/OSP :class:`StoreIndex`, :class:`DeviceStoreCache`, and
    pow2 delta buckets — so the whole incremental lifecycle (insert /
    delete / compact, version bumps, O(delta) post-mutation warmup) runs
    per shard, unchanged.

Join locality rules
-------------------
Two patterns' matching rows are guaranteed co-resident iff they bind a
shared variable from their SUBJECT position on both sides (both sides then
hash the binding to the same shard).  A chain of such links forces one
common subject variable, so the group planner simply buckets patterns by
subject variable: each group evaluates *entirely shard-local* through the
ordinary per-shard ``QueryEngine`` plans (slice / scan / INL, plan caches
and all).  Cross-group joins — object-keyed, e.g. Q4's ``?y`` — run as
DEVICE-SIDE HASH-REPARTITION JOINS: both sides bin their rows by a hash
of the join key, exchange the bins via ``lax.all_to_all`` inside one
shard_map, and each shard folds its received key-sorted runs with the
balanced partitioned-merge tree before joining SHARD-LOCAL — matching
rows co-hash, so the per-shard outputs union to exactly the global join
and no intermediate relation ever crosses back to the host.  A host fold
(all-gather the per-shard relations, balanced ``_merge_tree``, presorted
merge join) survives as the no-device dispatch path and the degradation
target for exchange faults.  Rewrite-mode type patterns bind ``?x`` from
BOTH endpoints (the range branch binds the object), so they are never
treated as co-hashed.

Execution lowers through ``jax.shard_map`` when the host actually has
``n_shards`` devices (the CI leg forces 8 with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``): per-shard stores
stack into ``[n_shards, ...]`` device buffers (a :class:`ShardStack`
mirrors the per-shard views with O(delta) refresh) and one shard-mapped
executable runs the group plan on every shard at once.  With fewer
devices the engine falls back to a per-shard dispatch loop — bit-identical
results, pinned by tests/test_shard.py.

Bulk ingest (``ShardedKB.ingest``) loads LUBM-100-class synthetic stores
(~1e7 triples): each part is encoded against the shared dictionary (host
searchsorted — the driver side of the paper's Spark pipeline), partitioned
by subject hash, and appended to the per-shard delta logs; lite/full
derivation happens lazily PER SHARD on first service of a mode, so no
single device ever materializes the whole store.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.abox import EncodedKB, encode_obe, tbox_term_map
from repro.core.closure import full_materialize
from repro.core.delta import DevStore, MODES, _delta_host
from repro.core.dictionary import (
    SENTINEL, sharded_dictionary_fn, sharded_out_specs, table_from_host,
)
from repro.core.engine import KnowledgeBase, PAPER_QUERIES, _raw_columns
from repro.core.index import pow2_bucket as _pow2
from repro.core.materialize import DeviceTBox, compact_rows, lite_materialize
from repro.core.query import (
    INVALID, Pattern, Relation, distinct, is_var, join, sig_label,
)
from repro.core.tbox import TBox, build_tbox
from repro.core.update import (
    DynamicDictionary, affected_instances, encode_delta,
    materialize_delta_mode, mentions_mask,
)
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY
from repro.testing import faults
from repro.testing.faults import FaultCrash, FaultError
from repro.utils import pair64

_EMPTY = np.zeros((0, 3), dtype=np.int32)
_HASH_MULT = np.uint64(0x9E3779B1)  # Fibonacci multiplicative hash

# failures the stacked shard_map path treats as "device down, fall back to
# the per-shard dispatch loop": injected transients + XLA runtime errors
_DEVICE_FAILURES = (FaultError, jax.errors.JaxRuntimeError)


def _local_mesh(n_shards: int, axis_name: str):
    """A 1-D mesh over this PROCESS's addressable devices.

    Single-process runtimes see every device, so this is `jax.make_mesh`
    verbatim there; under `jax.distributed` each process's stores live on
    its local devices only, and a mesh built from the global device list
    would try to address remote buffers.  (Cross-process global-mesh
    sharding is the remaining ROADMAP item-2 step.)
    """
    if jax.process_count() == 1:
        return jax.make_mesh((n_shards,), (axis_name,),
                             axis_types=(jax.sharding.AxisType.Auto,))
    devs = jax.local_devices()[:n_shards]
    return jax.sharding.Mesh(np.asarray(devs), (axis_name,))


def shard_of(ids, n_shards: int) -> np.ndarray:
    """Subject id -> shard id (deterministic multiplicative hash).

    Instance ids are dense ranks, so a plain modulo would couple shard
    choice to allocation order; the golden-ratio multiply decorrelates it.
    """
    h = (np.asarray(ids).astype(np.uint64) * _HASH_MULT) >> np.uint64(16)
    return (h % np.uint64(max(n_shards, 1))).astype(np.int64)


def partition_rows(rows: np.ndarray, n_shards: int) -> list:
    """Split (N, 3) encoded rows into per-shard arrays by subject hash."""
    rows = np.asarray(rows, dtype=np.int32).reshape(-1, 3)
    if rows.shape[0] == 0:
        return [_EMPTY] * n_shards
    sh = shard_of(rows[:, 0], n_shards)
    order = np.argsort(sh, kind="stable")
    rows_s, sh_s = rows[order], sh[order]
    bounds = np.searchsorted(sh_s, np.arange(n_shards + 1))
    return [rows_s[bounds[i]:bounds[i + 1]] for i in range(n_shards)]


def _exchange(parts_by_src: list, n_shards: int) -> list:
    """All-to-all: re-partition per-source derived rows by subject hash."""
    outs = [[] for _ in range(n_shards)]
    for rows in parts_by_src:
        for j, pr in enumerate(partition_rows(rows, n_shards)):
            if pr.shape[0]:
                outs[j].append(pr)
    return [np.concatenate(o) if o else _EMPTY for o in outs]


# ---------------------------------------------------------------------------
# ShardedKB: the partitioned KnowledgeBase facade
# ---------------------------------------------------------------------------


@dataclass
class IngestReport:
    """Structured per-part outcome of a streaming ingest.

    One entry per input part: ``dict(part=, ok=, attempts=, n_inserted=,
    version=)`` on success, ``dict(part=, ok=False, attempts=, error=)``
    after the retry budget is spent.  A failed part is *skipped* — the
    store stays at the consistent version the last successful part
    published — so callers inspect ``ok`` / ``failed`` instead of fishing
    a half-ingested store out of an exception.
    """

    parts: list = field(default_factory=list)
    n_retries: int = 0

    @property
    def failed(self) -> list:
        return [p for p in self.parts if not p["ok"]]

    @property
    def ok(self) -> bool:
        return not self.failed

    @property
    def n_rows(self) -> int:
        return sum(p.get("n_inserted", 0) for p in self.parts if p["ok"])


@dataclass
class ShardedKB:
    """Subject-hash partitioned KnowledgeBase with replicated TBox/dictionary.

    Mirrors the :class:`KnowledgeBase` surface (query / answers / insert /
    delete / compact / prewarm / warm_device / sizes) so servers and tests
    swap between the two; every result is pinned bit-identical to the
    single-device store in tests/test_shard.py.
    """

    shards: list  # per-shard KnowledgeBase
    dtb: DeviceTBox
    n_shards: int
    compact_threshold: float = 0.25
    version: int = 0
    n_new_terms: int = 0
    mat_counts: dict = field(
        default_factory=lambda: {"litemat": 0, "full": 0})
    _dyn: DynamicDictionary | None = field(default=None, repr=False)
    _engines: dict = field(default_factory=dict, repr=False)
    _pending: list = field(default_factory=list, repr=False)  # per-shard parts
    _mat_cursor: dict = field(
        default_factory=lambda: {"litemat": 0, "full": 0}, repr=False)
    # writers serialize here (same contract as KnowledgeBase.write_lock);
    # snapshot captures take it briefly to see a quiescent global version
    write_lock: threading.RLock = field(
        default_factory=threading.RLock, repr=False, compare=False)
    ingest_report: "IngestReport | None" = field(default=None, repr=False)
    # device-parallel dictionary encode (paper §III.B) for inserts: the
    # BULK-INGEST path flips this on — ids then assign in hash-partitioned
    # owner order, not global fp-rank order, so interactively built stores
    # keep the host encode (their id-space parity with a single
    # KnowledgeBase is pinned by the update oracle)
    use_sharded_encode: bool = False
    _enc_cache: dict = field(default_factory=dict, repr=False)

    # -- construction --------------------------------------------------------
    @classmethod
    def build(cls, raw, tbox: TBox | None = None, n_shards: int | None = None,
              parallel_tbox: bool = False) -> "ShardedKB":
        """Encode + partition + per-shard materialize (with exchange).

        The encode is the shared driver step (ids identical to the
        single-device build, so parity tests compare raw id sets); the
        lite/full materializers then run per shard over that shard's raw
        partition, and the derived rows are exchanged to THEIR subject's
        shard.  Per-shard MSC may keep a concept alongside a descendant
        held by another shard — answer-equivalent under interval
        evaluation, the same invariant the incremental-insert path pins.
        """
        tbox = tbox or build_tbox(raw.onto, parallel=parallel_tbox)
        n_shards = n_shards or max(jax.local_device_count(), 1)
        kbg = encode_obe(raw, tbox)
        dtb = DeviceTBox.build(tbox)
        parts = partition_rows(np.asarray(kbg.spo), n_shards)

        skb = cls(shards=[], dtb=dtb, n_shards=n_shards)
        lite_src, full_src, built = [], [], []
        for i, part in enumerate(parts):
            with skb._device_ctx(i):
                kb_i = EncodedKB(
                    spo=jnp.asarray(part), tables=kbg.tables, tbox=tbox,
                    n_instance_terms=kbg.n_instance_terms,
                    term_strings=kbg.term_strings)
                if part.shape[0]:
                    lite, lv, lstats = lite_materialize(kb_i, dtb)
                    full, fv, fstats = full_materialize(kb_i, dtb)
                    lite_src.append(np.asarray(compact_rows(lite, lv)))
                    full_src.append(np.asarray(compact_rows(full, fv)))
                else:
                    lstats = fstats = {}
                    lite_src.append(_EMPTY)
                    full_src.append(_EMPTY)
                built.append((kb_i, lstats, fstats))
        lite_parts = _exchange(lite_src, n_shards)
        full_parts = _exchange(full_src, n_shards)
        for i, (kb_i, lstats, fstats) in enumerate(built):
            with skb._device_ctx(i):
                K = KnowledgeBase(
                    kb=kb_i, dtb=dtb,
                    lite_spo=jnp.asarray(lite_parts[i]),
                    full_spo=jnp.asarray(full_parts[i]),
                    lite_stats=lstats, full_stats=fstats)
                skb.shards.append(K)
        skb._dyn = DynamicDictionary.from_kb(kbg)
        for K in skb.shards:
            K._dyn = skb._dyn  # one replicated growable dictionary
        return skb

    @classmethod
    def empty(cls, tbox: TBox, n_shards: int | None = None) -> "ShardedKB":
        """Shards over an empty ABox — the bulk-ingest starting point."""
        n_shards = n_shards or max(jax.local_device_count(), 1)
        fps, ids = tbox_term_map(tbox)
        ttable = table_from_host(fps, ids)
        dtb = DeviceTBox.build(tbox)
        skb = cls(shards=[], dtb=dtb, n_shards=n_shards)
        for i in range(n_shards):
            with skb._device_ctx(i):
                kb_i = EncodedKB(spo=jnp.asarray(_EMPTY), tables=(ttable,),
                                 tbox=tbox, n_instance_terms=0)
                skb.shards.append(KnowledgeBase(
                    kb=kb_i, dtb=dtb, lite_spo=jnp.asarray(_EMPTY),
                    full_spo=jnp.asarray(_EMPTY),
                    lite_stats={}, full_stats={}))
        skb._dyn = DynamicDictionary.from_kb(skb.shards[0].kb)
        for K in skb.shards:
            K._dyn = skb._dyn
        return skb

    @classmethod
    def ingest(cls, parts, tbox: TBox | None = None, onto=None,
               n_shards: int | None = None, max_part_retries: int = 3,
               backoff_s: float = 0.01, backoff_cap_s: float = 0.5,
               seed: int = 0) -> "ShardedKB":
        """Bulk-load an iterable of raw parts, never materializing globally.

        Each part (RawDataset or (s, p, o) fingerprint columns) is encoded
        against the growing replicated dictionary, hash-partitioned by
        subject, and appended to the per-shard raw logs; per-shard sorted
        indexes build lazily on first query and lite/full derivation is
        lazy per mode AND per shard (`_flush` derives each shard's backlog
        on its own device and exchanges the output) — the ROADMAP's
        LUBM-100-class loads stay out of single-device memory.

        The streaming loop is fault-tolerant: a part whose encode/partition
        fails transiently is retried up to ``max_part_retries`` times with
        jittered exponential backoff; a part that exhausts its budget (or
        hard-crashes with :class:`FaultCrash`) is recorded in the returned
        store's ``ingest_report`` and *skipped*, so a 10k-part stream never
        dies at part 7k — and because ``insert`` commits atomically (all
        fallible work precedes any store mutation), a failed part leaves
        the store at the consistent version the previous part published.
        """
        parts = iter(parts)
        if tbox is None:
            first = next(parts)
            tbox = build_tbox(onto or first.onto)
            parts = iter([first, *parts])
        skb = cls.empty(tbox, n_shards=n_shards)
        # encode is the ingest bottleneck: bulk loads take the device-side
        # parallel dictionary build whenever a device per shard exists
        skb.use_sharded_encode = True
        report = IngestReport()
        rng = np.random.default_rng(seed)
        for k, part in enumerate(parts):
            attempt = 0
            while True:
                v0 = skb.version
                try:
                    stats = skb.insert(part, auto_compact=False)
                    report.parts.append(dict(
                        part=k, ok=True, attempts=attempt + 1,
                        n_inserted=stats["n_inserted"],
                        version=skb.version))
                    break
                except Exception as e:  # noqa: BLE001 — classified below
                    retryable = (not isinstance(e, FaultCrash)
                                 and skb.version == v0  # nothing committed
                                 and attempt < max_part_retries)
                    if not retryable:
                        report.parts.append(dict(
                            part=k, ok=False, attempts=attempt + 1,
                            error=f"{type(e).__name__}: {e}"))
                        break
                    report.n_retries += 1
                    delay = min(backoff_cap_s, backoff_s * (2 ** attempt))
                    time.sleep(delay * (0.5 + 0.5 * rng.random()))
                    attempt += 1
        skb.ingest_report = report
        return skb

    # -- shard plumbing ------------------------------------------------------
    @property
    def kb(self) -> EncodedKB:
        """Replicated dictionary/TBox handle (shard 0's EncodedKB)."""
        return self.shards[0].kb

    @property
    def tbox(self) -> TBox:
        return self.kb.tbox

    def _device_ctx(self, i: int):
        devs = jax.local_devices()  # addressable from THIS process
        return jax.default_device(devs[i % len(devs)])

    def shard_devices(self) -> list:
        devs = jax.local_devices()
        return [devs[i % len(devs)] for i in range(self.n_shards)]

    def _sharded_encode_on(self) -> bool:
        return jax.local_device_count() >= self.n_shards > 1

    def _enc_executable(self, cap: int):
        """Cached shard_mapped sharded-dictionary build for one bin shape.

        Ids assign RELATIVE to 0 inside the executable; the host adds
        ``next_id`` afterwards — so the compiled build is reusable across
        batches as the dictionary grows.
        """
        fn = self._enc_cache.get(cap)
        if fn is None:
            body = sharded_dictionary_fn("d", self.n_shards, cap, base=0)
            mesh = _local_mesh(self.n_shards, "d")
            d = P("d")
            fn = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=(d, d, d),
                out_specs=sharded_out_specs(), check_vma=False))
            self._enc_cache[cap] = fn
        return fn

    def _encode_sharded(self, s_fp, p_fp, o_fp):
        """Device-parallel dictionary encode (the paper's §III.B) of a part.

        Predicates validate against the host mirror (the TBox-fixed OBE
        invariant ``encode_delta`` enforces); known s/o terms resolve by
        one host lookup; the UNKNOWN tail goes through ONE
        ``sharded_dictionary_fn`` pass — hash-partition to owner shards,
        per-owner unique + all_gather prefix-sum id ranges, reverse
        all_to_all — and the assigned (fp, id) pairs splice back into the
        host mirror via :meth:`DynamicDictionary.register`, so absorb /
        lookup / later host encodes see exactly the same dictionary.
        """
        p_ids = self._dyn.lookup(p_fp)
        bad = (p_ids < 0) | (p_ids >= self._dyn.instance_base)
        if bad.any():
            raise ValueError(
                "delta contains predicates outside the TBox property map — "
                "schema growth needs a re-encode (KnowledgeBase.build), the "
                "incremental path only grows the ABox")
        so_fp = np.concatenate([s_fp, o_fp])
        so_ids = self._dyn.lookup(so_fp)
        missing = so_ids < 0
        n_new = 0
        if missing.any():
            miss_fp = so_fp[missing]
            hi, lo = pair64.split_np(miss_fp)
            S, n = self.n_shards, hi.shape[0]
            cap = _pow2(-(-n // S), floor=256)
            hi_p = np.full(S * cap, int(SENTINEL), np.int32)
            lo_p = np.full(S * cap, int(SENTINEL), np.int32)
            valid = np.zeros(S * cap, bool)
            hi_p[:n], lo_p[:n], valid[:n] = hi, lo, True
            occ, table, overflow, _ = self._enc_executable(cap)(
                jnp.asarray(hi_p), jnp.asarray(lo_p), jnp.asarray(valid))
            if int(np.asarray(overflow).sum()):
                # a source shard holds at most cap occurrences and every
                # bin holds cap slots, so this is unreachable; guard the
                # invariant rather than silently dropping terms
                raise RuntimeError("sharded encode owner bins overflowed")
            base = self._dyn.next_id
            occ = np.asarray(occ).reshape(-1)[:n] + base
            thi = np.asarray(table[0]).reshape(-1)
            tlo = np.asarray(table[1]).reshape(-1)
            tids = np.asarray(table[2]).reshape(-1)
            real = tids >= 0
            fps_r = pair64.combine_np(thi[real], tlo[real])
            ufp, uidx = np.unique(fps_r, return_index=True)
            n_new = self._dyn.register(ufp, tids[real][uidx] + base)
            so_ids = so_ids.copy()
            so_ids[missing] = occ.astype(np.int32)
        s_ids, o_ids = np.split(so_ids, 2)
        spo = np.stack([s_ids, p_ids, o_ids], axis=1).astype(np.int32)
        return spo, n_new

    def _absorb(self, strings=None) -> int:
        """Fold freshly allocated dictionary terms into EVERY shard."""
        chunk = self._dyn.take_new_terms()
        if chunk is None:
            return 0
        fps, ids = chunk
        tbl = table_from_host(fps, ids)
        for K in self.shards:
            K.kb.tables = (*K.kb.tables, tbl)
            K.kb._merged = None
            K.kb.n_instance_terms += int(ids.shape[0])
        if strings:
            if self.kb.term_strings is None:
                shared = {}  # ONE dict, replicated by reference — every
                for K in self.shards:  # shard's extract sees every IRI
                    K.kb.term_strings = shared
            self.kb.term_strings.update(strings)
        return int(ids.shape[0])

    # -- lazy per-mode, per-shard derivation ---------------------------------
    def _flush(self, *modes: str) -> None:
        """Derive pending insert batches per shard, exchange, append.

        Each shard's share of the backlog is materialized on that shard's
        device (row-local derivation), then the derived rows are exchanged
        to their own subject's shard — range-derived type rows migrate,
        keeping the partition invariant.  Lazy per mode: a lite-only
        deployment never runs the full closure of its ingest.

        Crash-atomic per mode (same contract as KnowledgeBase._flush_mat):
        every batch is derived AND exchanged before any shard's log is
        appended, so a failure mid-derivation (fault site
        ``shard.flush_mat``) leaves every shard's published store
        consistent and a later flush retries the whole backlog.
        """
        n = len(self._pending)
        for mode in modes:
            if mode not in self._mat_cursor:
                continue
            cur = self._mat_cursor[mode]
            if cur >= n:
                continue
            with obs_trace.span("engine.flush", mode=mode, n_batches=n - cur,
                                sharded=True):
                staged = []
                for b, parts in enumerate(self._pending[cur:]):
                    derived_src = []
                    for i, part in enumerate(parts):
                        if part.shape[0] == 0:
                            derived_src.append(_EMPTY)
                            continue
                        faults.fire("shard.flush_mat", mode=mode, shard=i,
                                    batch=cur + b)
                        with self._device_ctx(i):
                            derived_src.append(
                                materialize_delta_mode(part, self.dtb, mode))
                    staged.append(_exchange(derived_src, self.n_shards))
                for exchanged in staged:
                    for j, rows in enumerate(exchanged):
                        self.shards[j].append_derived(mode, rows)
                    self.mat_counts[mode] += 1
                self._mat_cursor[mode] = n
                for K in self.shards:
                    K._bump()
        if self._pending and all(
                c >= n for c in self._mat_cursor.values()):
            self._pending.clear()
            self._mat_cursor = {m: 0 for m in self._mat_cursor}

    def _pending_rows(self, mode: str) -> int:
        if mode not in self._mat_cursor:
            return 0
        return sum(sum(int(p.shape[0]) for p in parts)
                   for parts in self._pending[self._mat_cursor[mode]:])

    # -- mutations -----------------------------------------------------------
    @property
    def delta_ratio(self) -> float:
        num = sum(self._pending_rows(m) for m in ("litemat", "full"))
        den = 0
        for K in self.shards:
            sizes = {"rewrite": K.kb.n,
                     "litemat": int(K.lite_spo.shape[0]),
                     "full": int(K.full_spo.shape[0])}
            den += sum(sizes.values())
            if K._delta is not None:
                for m in MODES:
                    num += K._delta.logs[m].n
                    if K._delta.base_alive[m] is not None:
                        num += sizes[m] - int(K._delta.base_alive[m].sum())
        return num / max(den, 1)

    def insert(self, raw, auto_compact: bool = True) -> dict:
        """Encode once (replicated dictionary), partition, append per shard.

        Commit-atomic: everything that can fail — the ``shard.ingest_encode``
        fault site, the host encode, the partition — runs BEFORE any shard
        log is touched; the per-shard appends are plain array concats.  The
        ingest retry loop relies on this: an exception here means nothing
        was committed and the published version is unchanged.
        """
        s_fp, p_fp, o_fp, strings = _raw_columns(raw)
        if s_fp.shape[0] == 0:
            return dict(n_inserted=0, n_new_terms=0)
        with self.write_lock:
            faults.fire("shard.ingest_encode", n=int(s_fp.shape[0]))
            if self.use_sharded_encode and self._sharded_encode_on():
                spo, n_new = self._encode_sharded(s_fp, p_fp, o_fp)
            else:
                spo, n_new = encode_delta(self._dyn, s_fp, p_fp, o_fp)
            parts = partition_rows(spo, self.n_shards)
            # -- commit point: nothing below raises -------------------------
            self._absorb(strings)
            for i, part in enumerate(parts):
                if part.shape[0]:
                    with self._device_ctx(i):
                        self.shards[i].append_raw(part)
                self.shards[i]._bump()
            self._pending.append(parts)
            self.n_new_terms += n_new
            self.version += 1
            stats = dict(
                n_inserted=int(spo.shape[0]), n_new_terms=n_new,
                n_pending_mat=sum(
                    self._pending_rows(m) for m in ("litemat", "full")),
                delta_ratio=round(self.delta_ratio, 4), version=self.version,
            )
            if auto_compact and self.delta_ratio > self.compact_threshold:
                stats["compacted"] = self.compact()
            return stats

    def delete(self, raw, auto_compact: bool = True) -> dict:
        """Coordinated delete: local tombstones, global repair frontier.

        Raw kills are shard-local (the triples live on their subject's
        shard); the affected-instance set is global, so every shard
        tombstones its derived mentions and contributes its live raw
        mentions to the frontier; the re-derived rows are exchanged back
        to their subjects' shards — the same exact-repair argument as the
        single-store delete, distributed.
        """
        s_fp, p_fp, o_fp, _ = _raw_columns(raw)
        if s_fp.shape[0] == 0:
            return dict(n_deleted=0)
        with self.write_lock:
            self._flush("litemat", "full")
            ids = np.stack([self._dyn.lookup(s_fp), self._dyn.lookup(p_fp),
                            self._dyn.lookup(o_fp)], axis=1)
            q = ids[(ids >= 0).all(axis=1)]
            deleted = []
            for i, part in enumerate(partition_rows(q, self.n_shards)):
                if part.shape[0]:
                    with self._device_ctx(i):
                        d = self.shards[i].kill_raw_rows(part)
                    if d.shape[0]:
                        deleted.append(d)
            if not deleted:
                return dict(n_deleted=0)
            deleted = np.concatenate(deleted)
            inst = affected_instances(deleted, self.tbox.instance_base)

            frontier_src = []
            for i, K in enumerate(self.shards):
                with self._device_ctx(i):
                    K.kill_derived_mentions(inst)
                    frontier_src.append(K.live_raw_mentions(inst))
            for mode in ("litemat", "full"):
                derived_src = []
                for i, rows in enumerate(frontier_src):
                    if rows.shape[0] == 0:
                        derived_src.append(_EMPTY)
                        continue
                    with self._device_ctx(i):
                        derived = materialize_delta_mode(rows, self.dtb, mode)
                        derived_src.append(
                            derived[mentions_mask(derived, inst)])
                for j, rows in enumerate(
                        _exchange(derived_src, self.n_shards)):
                    self.shards[j].append_derived(mode, rows)
            for K in self.shards:
                K._bump()
            self.version += 1
            stats = dict(
                n_deleted=int(deleted.shape[0]),
                n_affected_instances=int(inst.shape[0]),
                delta_ratio=round(self.delta_ratio, 4), version=self.version,
            )
            if auto_compact and self.delta_ratio > self.compact_threshold:
                stats["compacted"] = self.compact()
            return stats

    def compact(self, device: bool | None = None) -> dict:
        """Fold every shard's overlay into fresh per-shard bases."""
        with self.write_lock:
            if (all(K._delta is None or K._delta.empty for K in self.shards)
                    and not self._pending):
                return dict(compacted=False)
            with obs_trace.span("engine.compact", sharded=True,
                                n_shards=self.n_shards):
                self._flush("litemat", "full")
                sizes = {m: 0 for m in MODES}
                for i, K in enumerate(self.shards):
                    with self._device_ctx(i):
                        out = K.compact(device=device)
                    for m in MODES:
                        sizes[m] += int(out.get(m, 0))
                self.version += 1
            return dict(compacted=True, version=self.version, **sizes)

    # -- query surface -------------------------------------------------------
    def engine(self, mode: str = "litemat",
               use_index: bool = True) -> "ShardedQueryEngine":
        key = (mode, use_index)
        if key not in self._engines:
            self._engines[key] = ShardedQueryEngine(
                skb=self, mode=mode, use_index=use_index)
        return self._engines[key]

    def query(self, patterns, select=None, mode: str = "litemat",
              use_index: bool = True):
        return self.engine(mode, use_index).run(patterns, select=select)

    def answers(self, patterns, select=None, mode: str = "litemat",
                use_index: bool = True) -> set:
        rows, _ = self.query(patterns, select=select, mode=mode,
                             use_index=use_index)
        return {tuple(r) for r in rows.tolist()}

    def prewarm(self, queries=None, modes=("litemat",), buckets=(),
                use_index: bool = True) -> int:
        queries = (list(queries) if queries is not None
                   else list(PAPER_QUERIES.values()))
        return sum(self.engine(m, use_index).prewarm(queries, buckets=buckets)
                   for m in modes)

    def warm_device(self, mode: str = "litemat", keys=("scan", "pos")):
        """Per-shard device warmup (the O(delta)-per-shard unit)."""
        if mode in ("litemat", "full"):
            self._flush(mode)
        out = []
        for i, K in enumerate(self.shards):
            with self._device_ctx(i):
                out.append(K.warm_device(mode, keys=keys))
        return out

    def store_rows(self, mode: str = "litemat") -> np.ndarray:
        """Live rows of one store, all shards concatenated (host order)."""
        if mode in ("litemat", "full"):
            self._flush(mode)
        return np.concatenate(
            [np.asarray(K.store_rows(mode)) for K in self.shards])

    def device_buffers(self) -> list:
        """Sharded-engine device footprint beyond the per-shard stores:
        the ShardStack slabs every ShardedQueryEngine keeps resident.
        (Per-shard store buffers are reported by each shard's own
        KnowledgeBase, registered separately by :meth:`track_ledger`.)"""
        out = []
        for eng in self._engines.values():
            for stack in eng._stacks.values():
                out.extend(stack.device_buffers())
        return out

    def track_ledger(self) -> None:
        """Register this sharded store with the global resource ledger:
        each shard's KnowledgeBase under its shard index (per-shard
        ``hbm_bytes{shard=i}`` / live-triple gauges), plus the stacked
        shard_map slabs under ``shard="stack"``.  Idempotent; the ledger
        holds only weakrefs."""
        if getattr(self, "_ledger_handles", None):
            return
        from repro.obs.ledger import LEDGER

        self._ledger_handles = [
            LEDGER.track(str(i), K) for i, K in enumerate(self.shards)]
        self._ledger_handles.append(LEDGER.track("stack", self))

    def sizes(self) -> dict:
        out = {"original": 0, "lite": 0, "full": 0}
        for K in self.shards:
            s = K.sizes()
            out["original"] += s["original"]
            out["lite"] += s["lite"]
            out["full"] += s["full"]
        pending = sum(self._pending_rows(m) for m in ("litemat", "full"))
        delta = sum(K._delta.logs[m].n for K in self.shards
                    for m in MODES if K._delta is not None)
        if delta:
            out["delta_rows"] = delta
        if pending:
            out["delta_rows_pending_mat"] = pending
        return out


# ---------------------------------------------------------------------------
# Group planning: which joins stay shard-local
# ---------------------------------------------------------------------------


def _is_type_pattern(pat: Pattern, tbox) -> bool:
    return (not is_var(pat.p)) and (
        pat.p in ("rdf:type", "a") or pat.p == tbox.rdf_type_id)


def plan_groups(patterns, mode: str, tbox) -> list:
    """Bucket pattern indices by co-hashed subject variable.

    A pattern binds its subject variable from the co-hashed subject column
    — EXCEPT rewrite-mode type patterns, whose range branch binds the
    object — so patterns sharing a subject variable evaluate and join
    entirely shard-local; everything else is a singleton group combined
    globally.
    """
    groups: dict = {}
    for idx, pat in enumerate(patterns):
        local = is_var(pat.s) and not (
            mode == "rewrite" and _is_type_pattern(pat, tbox)
            and not is_var(pat.o))
        key = ("var", pat.s) if local else ("solo", idx)
        groups.setdefault(key, []).append(idx)
    return list(groups.values())


def _merge_tree(runs: list, key_col: int):
    """Balanced pairwise fold of key-sorted device runs into ONE sorted run.

    log2(k) merge levels instead of a left-deep fold: the accumulated run
    is never re-merged against every remaining part, so each row moves
    O(log k) times rather than O(k).  Each level pairs neighbours through
    ``ops.merge_gather`` (the device merge) + one row gather;
    INVALID keys sort last, so padded rows sink to the fold's tail.
    Shared by the host-fallback combine and the device repartition join's
    shard-local fold of exchanged partitions.
    """
    runs = list(runs)
    while len(runs) > 1:
        nxt = []
        for i in range(0, len(runs) - 1, 2):
            a, b = runs[i], runs[i + 1]
            ka, kb = a[:, key_col], b[:, key_col]
            g = ops.merge_gather(ka, jnp.zeros_like(ka), kb,
                                 jnp.zeros_like(kb))
            nxt.append(ops.two_source_gather(a, b, g))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return runs[0]


def _merge_shard_parts(parts: list, key_col: int):
    """Fold per-shard result rows into one key-sorted array on device.

    Each shard's rows sort locally (small — post-distinct relations), then
    fold through the balanced ``_merge_tree`` — so the combined relation
    arrives presorted for the join's build side without a global re-sort,
    and the single pad to the join capacity happens once downstream in
    ``_host_relation``, not per merge step.
    """
    live = [p for p in parts if p.shape[0]]
    if not live:
        return np.zeros((0, parts[0].shape[1]), np.int32)
    runs = [jnp.asarray(p[np.argsort(p[:, key_col], kind="stable")])
            for p in live]
    return np.asarray(_merge_tree(runs, key_col))


def _host_relation(gvars: tuple, rows: np.ndarray, cap: int) -> Relation:
    """(N, k) host rows -> INVALID-padded device Relation of capacity cap.

    This is the host-fold combine's re-upload point: every merged relation
    crosses host->device here.  The device repartition path never calls it
    mid-join, which the ``device/transfer_bytes{src=combine_upload}``
    counter pins in tests.
    """
    n = rows.shape[0]
    cols = np.full((len(gvars), cap), np.iinfo(np.int32).max, np.int32)
    cols[:, :n] = rows.T
    REGISTRY.counter("device/transfer_bytes",
                     src="combine_upload").inc(int(cols.nbytes))
    return Relation(
        vars=gvars, cols=jnp.asarray(cols),
        valid=jnp.arange(cap) < n, overflow=jnp.int32(max(n - cap, 0)))


def _bin_by_key(cols, valid, key_idx: int, n_shards: int):
    """Route one shard's relation rows to hash(join key) partitions.

    ``cols`` int32[V, cap] / ``valid`` bool[cap] -> int32[S, cap, V] send
    bins: bin t holds this shard's rows whose key hashes to t, ascending
    by key, INVALID-padded.  A bin can never overflow its ``cap`` slots —
    the source shard holds at most ``cap`` rows in total — so the exchange
    itself needs no overflow accounting (receive-side skew lands in the
    [S, cap] receive buffer, which holds the worst case of EVERY row
    hashing to one shard).  Invalid rows route nowhere.
    """
    n_vars, cap = cols.shape
    key = jnp.where(valid, cols[key_idx], INVALID)
    h = (key.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) >> jnp.uint32(16)
    tgt = jnp.where(valid & (key != INVALID),
                    (h % jnp.uint32(n_shards)).astype(jnp.int32),
                    jnp.int32(n_shards))
    order = jnp.lexsort((key, tgt))
    tgt_s = tgt[order]
    rows_s = cols.T[order]
    first = jnp.searchsorted(tgt_s, jnp.arange(n_shards, dtype=jnp.int32))
    slot = (jnp.arange(cap, dtype=jnp.int32)
            - first[jnp.clip(tgt_s, 0, n_shards - 1)])
    idx = jnp.where(tgt_s < n_shards, tgt_s * cap + slot, n_shards * cap)
    flat = jnp.full((n_shards * cap, n_vars), INVALID, jnp.int32)
    flat = flat.at[idx].set(rows_s, mode="drop")
    return flat.reshape(n_shards, cap, n_vars)


def _stack_parts(parts: list, n_vars: int, n_shards: int):
    """Host result parts -> stacked [S, V, cap] device relation.

    The repartition fold doesn't care how rows were distributed before the
    exchange (bins are computed from the rows themselves), so parts slot
    round-robin.  This is the single-device EMULATED entry into the device
    combine — the shard_map path hands over stacked buffers directly and
    never passes through here.
    """
    cap = _pow2(max((p.shape[0] for p in parts), default=1), floor=256)
    cols = np.full((n_shards, n_vars, cap), np.iinfo(np.int32).max, np.int32)
    valid = np.zeros((n_shards, cap), bool)
    for i, p in enumerate(parts):
        j = i % n_shards
        cols[j, :, :p.shape[0]] = p.T
        valid[j, :p.shape[0]] = True
    return jnp.asarray(cols), jnp.asarray(valid)


# ---------------------------------------------------------------------------
# ShardStack: stacked [n_shards, ...] device buffers for shard_map plans
# ---------------------------------------------------------------------------


class ShardStack:
    """Per-key stacked device buffers mirroring every shard's StoreView.

    The shard_map executables take ONE array per view key with a leading
    shard axis; this cache keeps those stacks resident and refreshes them
    with work independent of the base sizes: delta buckets re-upload
    O(n_shards * delta cap) rows, base tombstones land as point scatters,
    and base slabs re-upload only when a shard's base token changes
    (compaction) or the common pow2 capacity grows.  Every stack is placed
    with ``sharding`` (leading axis split over the shard mesh), so shard
    i's slab sits on shard i's device and the shard_map executable reads
    it in place.
    """

    def __init__(self, sharding):
        self.sharding = sharding
        self._states: dict = {}
        self._lock = threading.RLock()  # same contract as DeviceStoreCache
        self.stats = {"base_rebuilds": 0, "upload_base_rows": 0,
                      "upload_delta_rows": 0, "kill_scatter_rows": 0}

    def device_buffers(self) -> list:
        """Resident stacked slabs as ``(component, buf_id, nbytes)`` for
        the resource ledger — the shard_map path's device footprint."""
        out = []
        with self._lock:
            for st in self._states.values():
                out.append(("stack", id(st["base"]), st["base"].nbytes))
                out.append(("alive", id(st["alive"]), st["alive"].nbytes))
                if st["delta"] is not None:
                    out.append(("delta", id(st["delta"]),
                                st["delta"].nbytes))
                    out.append(("alive", id(st["dalive"]),
                                st["dalive"].nbytes))
        return out

    def _put(self, x):
        return jax.device_put(x, self.sharding)

    def _base_host(self, view, key):
        if key == "scan":
            return np.asarray(view.base_h)
        return view.base_index._h[view.base_index.perm(key).perm]

    def sync(self, views: list, key: str):
        with self._lock:
            return self._sync_locked(views, key)

    def _sync_locked(self, views: list, key: str):
        S = len(views)
        ncap = _pow2(max(v.base_n for v in views))
        has_delta = any(v.has_delta for v in views)
        dcap = _pow2(max(v.delta_n for v in views)) if has_delta else 0
        tokens = tuple(v.base_index.token for v in views)
        st = self._states.get(key)

        if st is None or st["ncap"] != ncap or st["tokens"] != tokens:
            self.stats["base_rebuilds"] += 1
            base = np.full((S, ncap, 3), np.iinfo(np.int32).max, np.int32)
            alive = np.zeros((S, ncap), bool)
            for i, v in enumerate(views):
                h = self._base_host(v, key)
                base[i, :h.shape[0]] = h
                if v.base_alive_h is None:
                    alive[i, :h.shape[0]] = True
                else:
                    ah = (v.base_alive_h if key == "scan"
                          else v.base_alive_h[v.base_index.perm(key).perm])
                    alive[i, :ah.shape[0]] = ah
                self.stats["upload_base_rows"] += int(h.shape[0])
                REGISTRY.counter("device/transfer_bytes",
                                 src="shard_stack").inc(int(h.nbytes))
            st = {"ncap": ncap, "tokens": tokens,
                  "base": self._put(base), "alive": self._put(alive),
                  "n_kills": [len(v.kills) for v in views],
                  "dcap": -1, "delta": None, "dalive": None,
                  "dstate": [None] * S}
            self._states[key] = st
        else:
            for i, v in enumerate(views):
                if len(v.kills) > st["n_kills"][i]:
                    idx = np.concatenate(v.kills[st["n_kills"][i]:])
                    if key != "scan":
                        idx = v.base_index.inv_perm(key)[idx]
                    pad = _pow2(idx.shape[0])
                    full = np.full(pad, ncap, np.int64)
                    full[:idx.shape[0]] = idx
                    st["alive"] = self._put(st["alive"].at[
                        i, jnp.asarray(full.astype(np.int32))].set(
                        False, mode="drop"))
                    self.stats["kill_scatter_rows"] += int(idx.shape[0])
                    st["n_kills"][i] = len(v.kills)

        dstate = [(v.delta_n, v.delta_mut) for v in views]
        if dcap != st["dcap"] or dstate != st["dstate"]:
            if not has_delta:
                st["delta"] = st["dalive"] = None
            else:
                drows = np.full((S, dcap, 3), np.iinfo(np.int32).max,
                                np.int32)
                dalive = np.zeros((S, dcap), bool)
                for i, v in enumerate(views):
                    if not v.has_delta:
                        continue
                    rows, al = _delta_host(v, key)
                    drows[i, :rows.shape[0]] = rows
                    dalive[i, :al.shape[0]] = al
                    self.stats["upload_delta_rows"] += dcap
                    REGISTRY.counter("device/transfer_bytes",
                                     src="shard_stack").inc(dcap * 12)
                st["delta"] = self._put(drows)
                st["dalive"] = self._put(dalive)
            st["dcap"] = dcap
            st["dstate"] = dstate
        return DevStore(base=st["base"], base_alive=st["alive"],
                        delta=st["delta"], delta_alive=st["dalive"])


# ---------------------------------------------------------------------------
# ShardedQueryEngine: group-local plans, global combine
# ---------------------------------------------------------------------------


@dataclass
class ShardedQueryEngine:
    """Executes conjunctive plans across a ShardedKB's shards.

    Subject-co-hashed groups run the full per-shard QueryEngine plans —
    through ONE shard_mapped executable when the host has a device per
    shard (per-shard sigs must agree; capacities unify to the max), else a
    per-shard dispatch loop (async across devices).  Cross-group joins
    all-gather the per-shard relations, fold them key-sorted with the
    device merge, and finish with the ordinary sort-merge join
    + distinct — bit-identical to the single-store engine.
    """

    skb: ShardedKB
    mode: str = "litemat"
    use_index: bool = True
    use_shard_map: bool | None = None  # None: auto (device per shard)
    # None: auto (repartition joins whenever shard_map is on); True forces
    # the device combine even on the per-shard loop path — the exchange
    # then runs its single-device EMULATION (transpose-as-all-to-all), the
    # same traced math minus the collective, which is how tests exercise
    # the fold on a one-device host
    use_repartition_join: bool | None = None
    _exec_cache: dict = field(default_factory=dict, repr=False)
    _stacks: dict = field(default_factory=dict, repr=False)
    _mesh: object = field(default=None, repr=False)
    cache_stats: dict = field(
        default_factory=lambda: {"hits": 0, "misses": 0,
                                 "shard_map_runs": 0, "loop_runs": 0,
                                 "shard_map_faults": 0,
                                 "repartition_runs": 0,
                                 "exchange_faults": 0},
        repr=False)

    def _engines(self):
        return [K.engine(self.mode, self.use_index) for K in self.skb.shards]

    def _shard_map_on(self) -> bool:
        if self.use_shard_map is not None:
            return self.use_shard_map
        return jax.local_device_count() >= self.skb.n_shards > 1

    def _repartition_on(self) -> bool:
        if self.use_repartition_join is not None:
            return self.use_repartition_join
        return self._shard_map_on()

    def prewarm(self, queries, buckets=(), select=None) -> int:
        n = 0
        if self.mode in ("litemat", "full"):
            self.skb._flush(self.mode)  # derive backlog: plans must see
        for pats in queries:  # the stores run() will execute against
            groups = plan_groups(pats, self.mode, self.skb.tbox)
            for g in groups:
                gpats = [pats[i] for i in g]
                gvars = _group_vars(gpats)
                for i, eng in enumerate(self._engines()):
                    if self.skb.shards[i].view(self.mode).n == 0:
                        continue
                    with self.skb._device_ctx(i):
                        n += eng.prewarm([gpats], buckets=buckets,
                                         select=gvars)
                if self._shard_map_on():
                    # the multi-device run() path executes the shard_mapped
                    # executable, not the per-shard plans — compile it too
                    before = self.cache_stats["misses"]
                    self._run_group_shard_map(gpats, gvars)
                    n += self.cache_stats["misses"] - before
        return n

    # -- group evaluation ----------------------------------------------------
    def _route_shards(self, gpats):
        """Constant-subject singleton groups touch only their owner shard."""
        if len(gpats) == 1 and not is_var(gpats[0].s):
            engines = self._engines()
            try:
                t = engines[0]._resolve(
                    gpats[0].s, "s",
                    _is_type_pattern(gpats[0], self.skb.tbox))
            except KeyError:
                return list(range(self.skb.n_shards))
            if t.hi == t.lo + 1 and not t.spills and t.members is None:
                return [int(shard_of(np.asarray([t.lo]),
                                     self.skb.n_shards)[0])]
        return list(range(self.skb.n_shards))

    def _run_group_loop(self, gpats, gvars):
        """Per-shard dispatch: each shard's own engine runs the group plan."""
        self.cache_stats["loop_runs"] += 1
        engines = self._engines()
        parts = []
        with obs_trace.span("shard.dispatch", path="loop",
                            n_shards=self.skb.n_shards):
            for i in self._route_shards(gpats):
                if self.skb.shards[i].view(self.mode).n == 0:
                    continue
                faults.fire("shard.query_shard", shard=i)
                with self.skb._device_ctx(i):
                    rows, _ = engines[i].run(gpats, select=gvars)
                if rows.shape[0]:
                    parts.append(np.asarray(rows, dtype=np.int32))
        return parts

    def _run_group_shard_map(self, gpats, gvars):
        """Shard_mapped group evaluation, results pulled back as host parts.

        Returns None (caller falls back to the loop) when per-shard plans
        disagree on signatures.  The repartition combine bypasses this
        wrapper and keeps ``_run_group_device``'s stacked buffers on
        device.
        """
        res = self._run_group_device(gpats, gvars)
        if res is None:
            return None
        cols, valid = res
        parts = []
        for i in range(self.skb.n_shards):
            n = int(valid[i].sum())
            if n:
                parts.append(np.asarray(cols[i])[:, :n].T.astype(np.int32))
        return parts

    def _run_group_device(self, gpats, gvars):
        """One shard_mapped executable evaluating the group plan per shard.

        Returns stacked device buffers ``(cols [S, V, cap], valid
        [S, cap])`` — or None when per-shard plans disagree on signatures:
        data-dependent strategy choices (single-predicate-run detection,
        INL conversion) can differ across shards.
        """
        engines = self._engines()
        plans = []
        for i, eng in enumerate(engines):
            with self.skb._device_ctx(i):
                plans.append(eng._plan(gpats, gvars))
        sigs0 = plans[0][0]
        if any(p[0] != sigs0 for p in plans[1:]):
            return None
        caps = tuple(max(p[2][j] for p in plans)
                     for j in range(len(plans[0][2])))
        join_cap = max(p[3] for p in plans)
        sel = plans[0][4]
        views = [K.view(self.mode) for K in self.skb.shards]
        ncap = _pow2(max(v.base_n for v in views))
        # slice-plan ranges address each shard's [real base | delta]
        # combined coordinates; the stacked slabs pad every base to ncap
        # rows, so per-shard delta ranges shift to start at ncap
        dyns_h = []
        for p, v in zip(plans, views):
            dyn = list(p[1])
            for j, sig in enumerate(sigs0):
                if sig.strategy == "slice" and v.base_n < ncap:
                    d = dict(dyn[j])
                    d["starts"] = jnp.where(
                        d["starts"] >= v.base_n,
                        d["starts"] + (ncap - v.base_n), d["starts"])
                    dyn[j] = d
            dyns_h.append(tuple(dyn))
        slabel = sig_label(sigs0)
        for attempt in range(6):
            stores = {}
            for k in {s.store for s in sigs0 if s.strategy in ("slice", "inl")}:
                stores[k] = self._stack(k).sync(views, k)
            if any(s.strategy == "scan" for s in sigs0):
                stores["scan"] = self._stack("scan").sync(views, "scan")
            has_delta = stores[next(iter(stores))].delta is not None
            dyns = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *dyns_h)
            fn = self._sm_executable(sigs0, caps, join_cap, sel, has_delta)
            cols, valid, overflow = fn(stores, dyns)
            ovf = np.asarray(overflow).reshape(-1)
            if int(ovf.max()) == 0:
                if attempt:
                    REGISTRY.histogram("join/capacity_depth",
                                       site="shard_map",
                                       sig=slabel).observe(attempt)
                self.cache_stats["shard_map_runs"] += 1
                return cols, valid
            # overflow is per shard: attribute the retry to each shard
            # whose buckets burst — lopsided counters here are the
            # hot-key-skew signal EXPLAIN surfaces host-side
            for i in np.nonzero(ovf)[0]:
                REGISTRY.counter("join/capacity_retry", site="shard_map",
                                 sig=slabel, shard=str(int(i))).inc()
            caps = tuple(c * 2 for c in caps)
            join_cap *= 2
        raise RuntimeError("sharded query kept overflowing its buckets")

    def _shard_mesh(self):
        if self._mesh is None:
            self._mesh = _local_mesh(self.skb.n_shards, "shard")
        return self._mesh

    def _stack(self, key: str) -> ShardStack:
        if key not in self._stacks:
            self._stacks[key] = ShardStack(
                NamedSharding(self._shard_mesh(), P("shard")))
        return self._stacks[key]

    def _sm_executable(self, sigs, caps, join_cap, sel, has_delta):
        from repro.core.query import _eval_inl, _eval_pattern

        key = ("sm", sigs, caps, join_cap, sel, has_delta)
        fn = self._exec_cache.get(key)
        if fn is not None:
            self.cache_stats["hits"] += 1
            return fn
        self.cache_stats["misses"] += 1

        def body(stores, dyns):
            st1 = {k: DevStore(
                base=v.base[0], base_alive=v.base_alive[0],
                delta=None if v.delta is None else v.delta[0],
                delta_alive=(None if v.delta_alive is None
                             else v.delta_alive[0]))
                for k, v in stores.items()}
            dyns1 = jax.tree_util.tree_map(lambda x: x[0], dyns)
            rel = None
            for sig, cap, dyn in zip(sigs, caps, dyns1):
                if sig.strategy == "inl":
                    rel, _ = _eval_inl(sig, cap, st1, dyn, rel)
                    continue
                r, _ = _eval_pattern(sig, cap, st1, dyn)
                rel = r if rel is None else join(rel, r, join_cap)
            out = distinct(rel, sel, join_cap)
            return out.cols[None], out.valid[None], out.overflow[None]

        f = jax.shard_map(body, mesh=self._shard_mesh(),
                          in_specs=(P("shard"), P("shard")),
                          out_specs=(P("shard"), P("shard"), P("shard")),
                          check_vma=False)
        fn = jax.jit(f)
        self._exec_cache[key] = fn
        return fn

    def _run_group(self, gpats, gvars):
        if self._shard_map_on():
            try:
                with obs_trace.span("shard.dispatch", path="shard_map",
                                    n_shards=self.skb.n_shards) as sp:
                    faults.fire("shard.shard_map")
                    parts = self._run_group_shard_map(gpats, gvars)
                    if parts is None:
                        sp.set_attr(plan_mismatch=True)
            except _DEVICE_FAILURES:
                # a device died under the stacked executable (or a test
                # injected one dying): degrade to the per-shard dispatch
                # loop, which re-syncs each shard independently
                self.cache_stats["shard_map_faults"] += 1
                REGISTRY.counter("shard/shard_map_faults").inc()
                obs_trace.event("shard_map_fallback")
                parts = None
            if parts is not None:
                return parts
        return self._run_group_loop(gpats, gvars)

    # -- device repartition combine ------------------------------------------
    def _cx_executable(self, acc_vars, rel_vars, key, acap, rcap, jcap):
        """One hash-repartition join step, cached per static shape/config.

        Both sides bin by hash(join key), exchange partitions (all-to-all
        under shard_map; a transpose in the single-device emulation), then
        each shard folds its received key-sorted runs with the balanced
        merge tree and runs the ordinary presorted merge join SHARD-LOCAL.
        Matching rows co-hash, so the per-shard join outputs union to
        exactly the global join — no intermediate relation ever crosses
        back to the host.
        """
        ck = ("cx", acc_vars, rel_vars, key, acap, rcap, jcap,
              self._shard_map_on())
        fn = self._exec_cache.get(ck)
        if fn is not None:
            self.cache_stats["hits"] += 1
            return fn
        self.cache_stats["misses"] += 1
        S = self.skb.n_shards
        ai, ri = acc_vars.index(key), rel_vars.index(key)

        def local_join(arecv, rrecv):
            # arecv [S, acap, Va] rows; rrecv [S, rcap, Vr] key-sorted runs
            m = _merge_tree([rrecv[i] for i in range(S)], ri)
            rel1 = Relation(vars=rel_vars, cols=m.T,
                            valid=m[:, ri] != INVALID,
                            overflow=jnp.int32(0))
            af = arecv.reshape(S * acap, len(acc_vars))
            acc1 = Relation(vars=acc_vars, cols=af.T,
                            valid=af[:, ai] != INVALID,
                            overflow=jnp.int32(0))
            out = join(rel1, acc1, jcap, a_sorted=True)
            return out.cols, out.valid, out.overflow

        if self._shard_map_on():
            def body(ac, av, rc, rv):
                abins = _bin_by_key(ac[0], av[0], ai, S)
                rbins = _bin_by_key(rc[0], rv[0], ri, S)
                arecv = jax.lax.all_to_all(abins, "shard", 0, 0)
                rrecv = jax.lax.all_to_all(rbins, "shard", 0, 0)
                cols, valid, ovf = local_join(arecv, rrecv)
                return cols[None], valid[None], ovf[None]

            f = jax.shard_map(body, mesh=self._shard_mesh(),
                              in_specs=(P("shard"),) * 4,
                              out_specs=(P("shard"),) * 3, check_vma=False)
        else:
            def f(ac, av, rc, rv):
                abins = jnp.stack(
                    [_bin_by_key(ac[i], av[i], ai, S) for i in range(S)])
                rbins = jnp.stack(
                    [_bin_by_key(rc[i], rv[i], ri, S) for i in range(S)])
                arecv = jnp.swapaxes(abins, 0, 1)
                rrecv = jnp.swapaxes(rbins, 0, 1)
                outs = [local_join(arecv[i], rrecv[i]) for i in range(S)]
                return (jnp.stack([o[0] for o in outs]),
                        jnp.stack([o[1] for o in outs]),
                        jnp.stack([o[2] for o in outs]))

        fn = jax.jit(f)
        self._exec_cache[ck] = fn
        return fn

    def _dx_executable(self, rvars, sel, cap):
        """Per-shard DISTINCT projection, cached per static shape/config."""
        ck = ("dx", rvars, sel, cap, self._shard_map_on())
        fn = self._exec_cache.get(ck)
        if fn is not None:
            self.cache_stats["hits"] += 1
            return fn
        self.cache_stats["misses"] += 1
        S = self.skb.n_shards

        def local(c, v):
            out = distinct(Relation(vars=rvars, cols=c, valid=v,
                                    overflow=jnp.int32(0)), sel, cap)
            return out.cols, out.valid

        if self._shard_map_on():
            def body(c, v):
                oc, ov = local(c[0], v[0])
                return oc[None], ov[None]

            f = jax.shard_map(body, mesh=self._shard_mesh(),
                              in_specs=(P("shard"),) * 2,
                              out_specs=(P("shard"),) * 2, check_vma=False)
        else:
            def f(c, v):
                outs = [local(c[i], v[i]) for i in range(S)]
                return (jnp.stack([o[0] for o in outs]),
                        jnp.stack([o[1] for o in outs]))

        fn = jax.jit(f)
        self._exec_cache[ck] = fn
        return fn

    def _run_repartition(self, patterns, groups, select, max_retries):
        """Evaluate groups, fold them with the device repartition join.

        Returns (rows, sel), or None when a shard_map group plan
        mismatched across shards — the caller then degrades to the host
        fold, exactly like the single-group dispatch does.
        """
        evaluated = []
        with obs_trace.span("shard.combine", path="repartition",
                            n_groups=len(groups)):
            for g in groups:
                gpats = [patterns[i] for i in g]
                gvars = _group_vars(gpats)
                if self._shard_map_on():
                    faults.fire("shard.shard_map")
                    res = self._run_group_device(gpats, gvars)
                    if res is None:
                        return None
                else:
                    res = _stack_parts(self._run_group_loop(gpats, gvars),
                                       len(gvars), self.skb.n_shards)
                evaluated.append((gvars, res))
            return self._combine_groups_device(evaluated, patterns, select,
                                               max_retries)

    def _combine_groups_device(self, evaluated, patterns, select,
                               max_retries):
        """Fold stacked per-shard group results entirely on device.

        Mirrors ``combine_groups``'s order (fewest rows first, greedy
        connected) and capacities, but every cross-group join runs as a
        hash-repartition join: intermediate relations stay stacked on
        devices between steps.  Only the final per-shard DISTINCT rows
        come back, and one host-side sorted-unique pass reproduces the
        global distinct's lexicographic order bit-for-bit.
        """
        all_vars = tuple(dict.fromkeys(
            v for pat in patterns for v in (pat.s, pat.p, pat.o)
            if is_var(v)))
        sel = tuple(select) if select else all_vars
        totals = [int(valid.sum()) for _, (_, valid) in evaluated]
        order = sorted(range(len(evaluated)), key=lambda i: totals[i])
        acc = None  # (vars, cols [S, V, cap], valid [S, cap])
        done = set()
        while len(done) < len(order):
            pick = None
            for i in order:
                if i in done:
                    continue
                gvars = evaluated[i][0]
                if acc is None or set(gvars) & set(acc[0]):
                    pick = i
                    break
            if pick is None:
                raise ValueError(
                    "cartesian products not supported — reorder the plan")
            done.add(pick)
            gvars, (cols, valid) = evaluated[pick]
            if acc is None:
                acc = (gvars, cols, valid)
                continue
            key = next(v for v in gvars if v in acc[0])
            faults.fire("shard.exchange")
            jcap = _pow2(max(totals[pick], int(acc[2].sum()), 1) * 2,
                         floor=256)
            plabel = sig_label(tuple((p.s, p.p, p.o) for p in patterns))
            for attempt in range(max_retries):
                fn = self._cx_executable(
                    acc[0], gvars, key, int(acc[1].shape[2]),
                    int(cols.shape[2]), jcap)
                ocols, ovalid, oovf = fn(acc[1], acc[2], cols, valid)
                if int(jnp.max(oovf)) == 0:
                    if attempt:
                        REGISTRY.histogram(
                            "join/capacity_depth", site="repartition",
                            sig=plabel, key=key).observe(attempt)
                    break
                ovf = np.asarray(oovf).reshape(-1)
                for i in (np.nonzero(ovf)[0] if ovf.shape[0] > 1 else [0]):
                    REGISTRY.counter("join/capacity_retry",
                                     site="repartition", sig=plabel,
                                     shard=str(int(i))).inc()
                jcap *= 2
            else:
                raise RuntimeError("sharded join kept overflowing")
            out_vars = tuple(gvars) + tuple(
                v for v in acc[0] if v not in gvars)
            acc = (out_vars, ocols, ovalid)
        self.cache_stats["repartition_runs"] += 1
        REGISTRY.counter("shard/combine_runs", path="repartition").inc()
        # per-shard distinct shrinks the readback; identical sel-tuples can
        # still straddle shards when sel drops the last join key, so one
        # host-side sorted-unique pass finishes the global dedup in the
        # same ascending-lexicographic order `distinct` emits
        dfn = self._dx_executable(acc[0], sel, int(acc[1].shape[2]))
        dcols, dvalid = dfn(acc[1], acc[2])
        parts = []
        for i in range(self.skb.n_shards):
            n = int(dvalid[i].sum())
            if n:
                parts.append(np.asarray(dcols[i])[:, :n].T.astype(np.int32))
        if not parts:
            return np.zeros((0, len(sel)), np.int32), sel
        return np.unique(np.concatenate(parts), axis=0), sel

    # -- the full query ------------------------------------------------------
    def run(self, patterns, select=None, max_retries: int = 6):
        """Execute; returns (rows int32[k, n_select], select var names).

        Same contract as QueryEngine.run: rows are DISTINCT bindings of the
        selected variables, in the global lexicographic order the distinct
        pass produces — bit-identical to the single-device engine given the
        same ``select``.  Multi-group plans (cross-shard, object-keyed
        joins) fold through the device-side hash-repartition join when
        enabled, degrading to the host fold on exchange faults or plan
        mismatches.
        """
        patterns = list(patterns)
        if self.mode in ("litemat", "full"):
            self.skb._flush(self.mode)
        groups = plan_groups(patterns, self.mode, self.skb.tbox)
        if len(groups) > 1 and self._repartition_on():
            try:
                out = self._run_repartition(patterns, groups, select,
                                            max_retries)
                if out is not None:
                    return out
            except _DEVICE_FAILURES:
                self.cache_stats["exchange_faults"] += 1
                REGISTRY.counter("shard/exchange_faults").inc()
                obs_trace.event("repartition_fallback")
            REGISTRY.counter("shard/combine_runs", path="host_fallback").inc()
        else:
            REGISTRY.counter("shard/combine_runs", path="host").inc()
        evaluated = []
        for g in groups:
            gpats = [patterns[i] for i in g]
            gvars = _group_vars(gpats)
            evaluated.append((gvars, self._run_group(gpats, gvars)))
        return combine_groups(evaluated, patterns, select,
                              max_retries=max_retries)


def combine_groups(evaluated, patterns, select=None, max_retries: int = 6):
    """Fold per-group, per-shard result parts into the final distinct rows.

    ``evaluated`` is ``[(group_vars, [int32[k_i, |vars|] per shard]), ...]``
    in plan-group order.  Groups fold through presorted merge joins, then
    one global distinct (cross-shard duplicates of object-keyed bindings
    collapse here) — shared by the live ShardedQueryEngine and the pinned
    per-shard snapshot reads (core/snapshot.py), so both produce
    bit-identical rows from identical parts.
    """
    all_vars = tuple(dict.fromkeys(
        v for pat in patterns for v in (pat.s, pat.p, pat.o)
        if is_var(v)))
    sel = tuple(select) if select else all_vars

    order = sorted(range(len(evaluated)),
                   key=lambda i: sum(p.shape[0] for p in evaluated[i][1]))
    acc = None
    done = set()
    while len(done) < len(order):
        pick = None
        for i in order:
            if i in done:
                continue
            gvars = evaluated[i][0]
            if acc is None or set(gvars) & set(acc.vars):
                pick = i
                break
        if pick is None:
            raise ValueError(
                "cartesian products not supported — reorder the plan")
        done.add(pick)
        gvars, parts = evaluated[pick]
        total = sum(p.shape[0] for p in parts)
        if acc is None:
            cap = _pow2(total, floor=256)
            rows = (np.concatenate(parts) if parts
                    else np.zeros((0, len(gvars)), np.int32))
            acc = _host_relation(gvars, rows, cap)
            continue
        key = next(v for v in gvars if v in acc.vars)
        merged = _merge_shard_parts(
            parts, gvars.index(key)) if parts else np.zeros(
            (0, len(gvars)), np.int32)
        rel = _host_relation(gvars, merged, _pow2(total, floor=256))
        jcap = _pow2(max(total, _acc_rows(acc), 1) * 2, floor=256)
        plabel = sig_label(tuple((p.s, p.p, p.o) for p in patterns))
        for attempt in range(max_retries):
            out = join(rel, acc, jcap, a_sorted=True)
            if int(out.overflow) == 0:
                if attempt:
                    REGISTRY.histogram("join/capacity_depth",
                                       site="host_fold", sig=plabel,
                                       key=key).observe(attempt)
                break
            # host fold sees already-merged parts: no per-shard overflow
            # attribution exists, so the retry lands on shard="global"
            REGISTRY.counter("join/capacity_retry", site="host_fold",
                             sig=plabel, shard="global").inc()
            jcap *= 2
        else:
            raise RuntimeError("sharded join kept overflowing")
        acc = out
    out = distinct(acc, sel, _pow2(_acc_rows(acc), floor=256))
    n = int(out.valid.sum())
    rows = np.asarray(out.cols)[:, :n].T
    return rows, sel


def _acc_rows(rel: Relation) -> int:
    return int(rel.valid.sum())


def _group_vars(gpats) -> tuple:
    return tuple(dict.fromkeys(
        v for pat in gpats for v in (pat.s, pat.p, pat.o) if is_var(v)))


def assert_partitioned(skb: ShardedKB) -> None:
    """Test hook: every live row of every store sits on its subject's shard."""
    for mode in MODES:
        skb._flush(mode) if mode in ("litemat", "full") else None
        for i, K in enumerate(skb.shards):
            rows = np.asarray(K.store_rows(mode))
            if rows.shape[0] == 0:
                continue
            sh = shard_of(rows[:, 0], skb.n_shards)
            assert (sh == i).all(), (mode, i, rows[sh != i][:5])


__all__ = ["ShardedKB", "ShardedQueryEngine", "ShardStack", "IngestReport",
           "shard_of", "partition_rows", "plan_groups", "combine_groups",
           "assert_partitioned"]
