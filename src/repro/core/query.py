"""Conjunctive SPARQL evaluation over encoded triples — the paper's §V.

Three execution modes, matching the paper's Table VI columns:

  * ``litemat``  — interval predicates (one compare per sub-hierarchy) over
                   the lite-materialized store,
  * ``full``     — plain equality over the fully materialized store,
  * ``rewrite``  — the no-materialization baseline: constants expanded
                   host-side to their sub-concept/property id sets,
                   evaluated as OR-filters (the paper's optimized
                   "conjunction of OR subqueries" formulation).

The algebra is the paper's filter→map→join pipeline, in XLA static-shape
discipline: every operator carries a static capacity + validity mask +
overflow counter, and the engine re-executes with doubled capacities if an
overflow is reported (power-of-two buckets keep recompiles bounded).

Stores are *live*: the engine executes against a StoreView (core/delta.py)
— an immutable base plus a small delta overlay with tombstones — so the
same compiled plans serve a store that is being mutated between queries.
Patterns union base-index slices with delta-index slices, and every row
carries a liveness bit that the gather/compaction paths filter.  Each view
key reaches the device as a PAIR of arrays — the base store (resident,
untouched by mutations) and a power-of-two delta bucket — addressed in
combined coordinates, so refreshing the executable's inputs after an
insert/delete moves O(delta) bytes, never an O(base) re-concatenation.

Execution strategy per pattern (chosen host-side during planning):

  * ``slice`` — any litemat/full pattern with at least one pure-interval
    constant resolves against the sorted store permutations (core/index.py
    via the view): POS/PSO for constant predicates, SPO/OSP for constant
    subject/object patterns with a *variable* predicate.  O(log N) host
    binary searches yield contiguous row ranges (base + delta, one per
    spill interval), and the device work is a single contiguous gather.
    The range lengths give the planner cardinalities with zero device
    passes.
  * ``scan``  — residual patterns (rewrite mode, member sets) stream the
    store once through the Pallas compaction kernel
    (kernels/stream_compact.py).  Simple interval predicates fuse the
    filter AND the tombstone mask into the same kernel pass; the
    compaction's total doubles as the match count, so there is no separate
    counting pass at execution time.

Every (mode, pattern-signature, capacity-bucket) combination is lowered to
ONE jitted executable and memoized in ``QueryEngine._exec_cache``: repeated
queries — and *parameterized* queries that differ only in constants, which
enter the trace as device scalars — reuse the compiled plan instead of
retracing XLA.  ``prewarm`` pre-traces the executables for a query set at
its natural capacity buckets (plus caller-chosen growth buckets), removing
the first-query-per-bucket cold start.

Beyond the paper (it declares join ordering out of scope): the planner joins
in ascending-cardinality order, which also gives capacity estimates.
"""
from __future__ import annotations

import hashlib
import threading
import time
from dataclasses import dataclass, field
from functools import partial, wraps

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.abox import EncodedKB
from repro.core.delta import StoreView
from repro.core.index import StoreIndex, key_cols, pow2_bucket as _pow2
from repro.core.materialize import DeviceTBox
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.obs.metrics import REGISTRY
from repro.utils import pair64
from repro.utils.parallel import run_concurrently

INVALID = jnp.int32(np.iinfo(np.int32).max)
_EXEC_LOCK = threading.Lock()  # plan-cache check-and-insert
_I32_MIN = int(np.iinfo(np.int32).min)
_I32_MAX = int(np.iinfo(np.int32).max)


def is_var(t) -> bool:
    return isinstance(t, str) and t.startswith("?")


def sig_label(sigs) -> str:
    """Compact, stable metric label for a plan's signature tuple.

    ``"<n>p:<hex10>"`` — pattern count plus a 10-hex-digit blake2s digest
    of the PatternSig tuple's repr.  PatternSig fields are primitives, so
    the repr (and hence the label) is identical across processes: the
    per-signature compile/retry metrics labelled with it merge cleanly in
    a fleet aggregation, and label cardinality stays bounded by the number
    of distinct plans rather than distinct queries.
    """
    digest = hashlib.blake2s(repr(tuple(sigs)).encode(),
                             digest_size=5).hexdigest()
    return f"{len(sigs)}p:{digest}"


@dataclass(frozen=True)
class Pattern:
    s: object  # '?var' | name str | raw int id
    p: object
    o: object


@dataclass
class Term:
    """A resolved pattern constant: interval [lo, hi) + optional spills/set."""

    lo: int
    hi: int
    spills: tuple = ()  # ((lo, hi), ...)
    members: np.ndarray | None = None  # explicit id set (rewrite mode)

    def intervals(self):
        return [(self.lo, self.hi)] + list(self.spills)


# ---------------------------------------------------------------------------
# Static plan signatures vs dynamic (traced) constants
#
# A query plan is split into a hashable *signature* — everything that shapes
# the XLA computation — and a pytree of device scalars/arrays that enter the
# trace as arguments.  Two queries with the same signature share one
# compiled executable regardless of their constants.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class TermSig:
    kind: str  # 'interval' | 'members'
    n_spills: int = 0
    mem_cap: int = 0  # padded power-of-two member-set length


@dataclass(frozen=True)
class PatternSig:
    pvars: tuple  # per-position var name or None
    strategy: str  # 'slice' | 'scan' | 'inl'
    s_sig: TermSig | None = None
    p_sig: TermSig | None = None
    o_sig: TermSig | None = None
    store: str = "pos"  # slice/inl: which sorted permutation
    k: int = 1  # slice: number of contiguous ranges
    residual: tuple = ()  # slice/inl: positions re-checked after the gather
    # rewrite type pattern: (dom_cap, rng_cap, has_dom, has_rng) — the flags
    # are static so empty domain/range branches compile to nothing
    extra_caps: tuple | None = None
    fused: bool = False  # scan: predicate fused into the compaction kernel
    probe_pos: int = -1  # inl: pattern position the bound var probes (0|2)
    n_pids: int = 0  # inl: how many distinct store pids are probed


def _clip32(v) -> int:
    return int(np.clip(int(v), _I32_MIN, _I32_MAX))


def _pad_set(ids: np.ndarray):
    """Sorted id set -> (pow2 bucket, INT32_MAX-padded device array)."""
    cap = _pow2(len(ids))
    out = np.full(cap, _I32_MAX, np.int32)
    out[: len(ids)] = ids
    return cap, jnp.asarray(out)


def _lower_term(t: Term | None):
    """Host Term -> (static TermSig, traced int32 array) or (None, None)."""
    if t is None:
        return None, None
    if t.members is not None:
        cap, mem = _pad_set(t.members)
        return TermSig("members", mem_cap=cap), mem
    vals = [_clip32(t.lo), _clip32(t.hi)]
    for lo, hi in t.spills:
        vals += [_clip32(lo), _clip32(hi)]
    return (TermSig("interval", n_spills=len(t.spills)),
            jnp.asarray(np.asarray(vals, np.int32)))


def _term_mask_dyn(col, sig: TermSig, vals):
    """Per-column membership mask with traced bounds (spill count static)."""
    if sig.kind == "members":
        pos = jnp.clip(jnp.searchsorted(vals, col), 0, vals.shape[0] - 1)
        return (vals[pos] == col) & (col != INVALID)
    m = (col >= vals[0]) & (col < vals[1])
    for i in range(sig.n_spills):
        m = m | ((col >= vals[2 + 2 * i]) & (col < vals[3 + 2 * i]))
    return m


def _in_set(col, arr):
    """Sorted-membership test; arr is INT32_MAX-padded (possibly all-pad)."""
    pos = jnp.clip(jnp.searchsorted(arr, col), 0, arr.shape[0] - 1)
    return (arr[pos] == col) & (col != INVALID)


def _pattern_const_key(terms):
    """Hashable snapshot of a pattern's resolved constants.

    The probe-constant half of the ``(PatternSig, bucket)`` selectivity
    key: two patterns lowering to the same signature but resolving
    different constants (Q3's Professors vs Q4's Chairs) get distinct
    buckets, so one's observation never aliases the other's plan.
    """
    return tuple(
        None if t is None else
        (t.lo, t.hi, t.spills,
         None if t.members is None else t.members.tobytes())
        for t in terms)


def _type_rewrite_masks_dyn(spo, alive, mem, tid, dom, rng, has_dom, has_rng):
    """Rewrite-mode (?x rdf:type C): explicit ∪ domain ∪ range branches.

    Returns (mask_s, mask_o): rows binding ?x to their SUBJECT (explicit
    type triples and domain-entailing predicates) and rows binding ?x to
    their OBJECT (range-entailing predicates; None when the target has no
    range-entailing properties — statically known, so the branch compiles
    to nothing) — the full RDFS reformulation the paper's Q4' illustrates.
    The branches are NOT exclusive: a triple whose predicate entails the
    target through both its domain and its range contributes BOTH
    endpoints, so the two masks must be compacted separately (collapsing
    them to one row/one binding silently undercounts — the drift the
    differential oracle caught).
    """
    s, p, o = spo[:, 0], spo[:, 1], spo[:, 2]
    valid = (s != INVALID) & alive
    m_s = (p == tid) & _in_set(o, mem)
    if has_dom:
        m_s = m_s | _in_set(p, dom)
    m_o = (_in_set(p, rng) & valid) if has_rng else None
    return m_s & valid, m_o


def _scan_mask(sig: PatternSig, spo, alive, dyn):
    """Full-store boolean mask for a scan pattern (non-fused path)."""
    s, p, o = spo[:, 0], spo[:, 1], spo[:, 2]
    mask = (s != INVALID) & alive
    for tsig, col, key in ((sig.s_sig, s, "s"), (sig.p_sig, p, "p"),
                           (sig.o_sig, o, "o")):
        if tsig is not None:
            mask = mask & _term_mask_dyn(col, tsig, dyn[key])
    return mask, None


# ---------------------------------------------------------------------------
# Relations: struct-of-arrays with validity + overflow accounting
# ---------------------------------------------------------------------------


@dataclass
class Relation:
    vars: tuple  # var names, host
    cols: jnp.ndarray  # int32[n_vars, cap]
    valid: jnp.ndarray  # bool[cap]
    overflow: jnp.ndarray  # int32 scalar (rows that did not fit)

    @property
    def cap(self) -> int:
        return int(self.valid.shape[0])

    def col(self, v) -> jnp.ndarray:
        return self.cols[self.vars.index(v)]


def _build_relation(pvars, s, p, o, ok, total, cap: int) -> Relation:
    """Assemble a Relation from gathered columns + validity.

    Handles repeated variables within one pattern (equality constraint) the
    same way for both strategies.
    """
    cols = []
    seen = {}
    eq = None
    for v, colv in zip(pvars, (s, p, o)):
        if v is None:
            continue
        if v in seen:  # repeated var in one pattern: equality constraint
            eq = (seen[v], colv)
            continue
        seen[v] = colv
        cols.append(colv)
    if eq is not None:
        ok = ok & (eq[0] == eq[1])
    cols = [jnp.where(ok, c, INVALID) for c in cols]
    return Relation(
        vars=tuple(seen),
        cols=jnp.stack(cols) if cols else jnp.zeros((0, cap), jnp.int32),
        valid=ok,
        overflow=jnp.maximum(total - cap, 0),
    )


def _gather_ranges(base, base_alive, delta, delta_alive, starts, lens,
                   cap: int):
    """Concatenate k contiguous row ranges of a sorted view into [cap] rows.

    Ranges address the virtual [base | delta-bucket] concatenation (delta
    offset by the base row count); rows resolve through a two-source gather
    so the base array is never physically concatenated with the delta.
    Liveness filters tombstoned rows out of the gathered slice: dead rows
    keep their slot (totals stay exact range lengths for overflow
    accounting) but are invalidated before the relation is built.
    """
    src, ok, total, _ = ops.segment_positions(starts, lens, cap)
    rows = ops.two_source_gather(base, delta, src)
    alive = ops.two_source_gather(base_alive, delta_alive, src)
    return rows, ok & alive, total


def _stitch_compact(take_b, total_b, take_d, total_d, base_n: int, cap: int):
    """Fuse two per-source compactions into one combined-coordinate take.

    Base matches come first (they are base-store row indices as-is), delta
    matches follow offset by ``base_n`` — the same combined addressing the
    range lookups use, so downstream gathers are shared with the slice path.
    """
    j = jnp.arange(cap, dtype=jnp.int32)
    use_b = j < total_b
    di = jnp.clip(j - total_b, 0, cap - 1)
    take = jnp.where(use_b, take_b, base_n + take_d[di])
    total = total_b + total_d
    return take, j < jnp.minimum(total, cap), total


def _masked_compact_both(ds, mask_b, mask_d, cap: int):
    """Compact one mask per source and stitch into combined coordinates."""
    take_b, ok_b, tb = ops.compact_indices(
        mask_b, cap, block=ops.auto_block(mask_b.shape[0]))
    if mask_d is None:  # delta-free view: single-source plan
        return take_b, ok_b, tb
    take_d, _, td = ops.compact_indices(
        mask_d, cap, block=ops.auto_block(mask_d.shape[0]))
    return _stitch_compact(take_b, tb, take_d, td, ds.base.shape[0], cap)


def _dual_masked_compact_both(ds, ms_b, mo_b, ms_d, mo_d, cap: int):
    """Compact BOTH rewrite branches of each source in one dual-mask pass.

    The subject-binding and object-binding masks cover the same rows, so
    the dual-mask kernel emits both compacted streams per tile — one grid
    pass over each source instead of two.  Returns the two stitched
    (take, ok, total) triples in combined [base | delta] coordinates.
    """
    take_s_b, ok_s_b, ts_b, take_o_b, ok_o_b, to_b = ops.dual_compact_indices(
        ms_b, mo_b, cap, block=ops.auto_block(ms_b.shape[0]))
    if ms_d is None:  # delta-free view
        return (take_s_b, ok_s_b, ts_b), (take_o_b, ok_o_b, to_b)
    take_s_d, _, ts_d, take_o_d, _, to_d = ops.dual_compact_indices(
        ms_d, mo_d, cap, block=ops.auto_block(ms_d.shape[0]))
    base_n = ds.base.shape[0]
    return (_stitch_compact(take_s_b, ts_b, take_s_d, ts_d, base_n, cap),
            _stitch_compact(take_o_b, to_b, take_o_d, to_d, base_n, cap))


def _rewrite_type_bindings(sig: PatternSig, ds, dyn, cap: int):
    """Rewrite-mode type pattern -> (ok, total, xcol of ?x bindings).

    Subject-binding rows (explicit/domain) and object-binding rows (range)
    are compacted INDEPENDENTLY per source and their bound values stitched:
    a row entailing the target through both branches yields two bindings.
    With a range branch, both masks compact in ONE dual-mask kernel pass
    per source.
    """
    _, _, has_dom, has_rng = sig.extra_caps
    sets = (dyn["o"], dyn["tid"], dyn["dom"], dyn["rng"], has_dom, has_rng)
    ms_b, mo_b = _type_rewrite_masks_dyn(ds.base, ds.base_alive, *sets)
    ms_d = mo_d = None
    if ds.delta is not None:
        ms_d, mo_d = _type_rewrite_masks_dyn(ds.delta, ds.delta_alive, *sets)
    if not has_rng:  # no object branch: the subject stream is the answer
        take_s, ok_s, total_s = _masked_compact_both(ds, ms_b, ms_d, cap)
        vals_s = ops.two_source_gather(ds.base, ds.delta, take_s)[:, 0]
        return ok_s, total_s, vals_s
    (take_s, ok_s, total_s), (take_o, _, total_o) = _dual_masked_compact_both(
        ds, ms_b, mo_b, ms_d, mo_d, cap)
    vals_s = ops.two_source_gather(ds.base, ds.delta, take_s)[:, 0]
    vals_o = ops.two_source_gather(ds.base, ds.delta, take_o)[:, 2]
    j = jnp.arange(cap, dtype=jnp.int32)
    use_s = j < total_s
    vo = vals_o[jnp.clip(j - total_s, 0, cap - 1)]
    xcol = jnp.where(use_s, vals_s, vo)
    total = total_s + total_o
    return j < jnp.minimum(total, cap), total, xcol


def _scan_compact(sig: PatternSig, ds, dyn, cap: int):
    """Scan both sources of a view key -> (take, ok, total)."""
    base_n = ds.base.shape[0]
    if sig.fused:
        pv, ov = dyn.get("p"), dyn.get("o")
        plo = pv[0] if pv is not None else jnp.int32(_I32_MIN)
        phi = pv[1] if pv is not None else jnp.int32(_I32_MAX)
        olo = ov[0] if ov is not None else jnp.int32(_I32_MIN)
        ohi = ov[1] if ov is not None else jnp.int32(_I32_MAX)
        params = jnp.stack([plo, phi, olo, ohi]).astype(jnp.int32)
        take_b, ok_b, tb = ops.masked_interval_compact(
            ds.base[:, 1], ds.base[:, 2], ds.base_alive, params, cap,
            block=ops.auto_block(base_n))
        if ds.delta is None:
            return take_b, ok_b, tb
        take_d, _, td = ops.masked_interval_compact(
            ds.delta[:, 1], ds.delta[:, 2], ds.delta_alive, params, cap,
            block=ops.auto_block(ds.delta.shape[0]))
        return _stitch_compact(take_b, tb, take_d, td, base_n, cap)
    mask_b, _ = _scan_mask(sig, ds.base, ds.base_alive, dyn)
    mask_d = (None if ds.delta is None
              else _scan_mask(sig, ds.delta, ds.delta_alive, dyn)[0])
    return _masked_compact_both(ds, mask_b, mask_d, cap)


def _eval_pattern(sig: PatternSig, cap: int, stores, dyn):
    """One pattern -> (Relation, match count), inside the jitted executable."""
    if sig.strategy == "slice":
        ds = stores[sig.store]
        g, ok, total = _gather_ranges(ds.base, ds.base_alive, ds.delta,
                                      ds.delta_alive, dyn["starts"],
                                      dyn["lens"], cap)
        s, p, o = g[:, 0], g[:, 1], g[:, 2]
        for posi in sig.residual:
            tsig = (sig.s_sig, sig.p_sig, sig.o_sig)[posi]
            key = ("s", "p", "o")[posi]
            ok = ok & _term_mask_dyn((s, p, o)[posi], tsig, dyn[key])
        return _build_relation(sig.pvars, s, p, o, ok, total, cap), total

    ds = stores["scan"]
    if sig.extra_caps is not None:  # rewrite-mode type pattern (?x rdf:type C)
        ok, total, xcol = _rewrite_type_bindings(sig, ds, dyn, cap)
        var = next(v for v in sig.pvars if v is not None)
        cols = [jnp.where(ok, xcol, INVALID)]
        rel = Relation(vars=(var,), cols=jnp.stack(cols), valid=ok,
                       overflow=jnp.maximum(total - cap, 0))
        return rel, total
    take, ok, total = _scan_compact(sig, ds, dyn, cap)
    g = ops.two_source_gather(ds.base, ds.delta, take)
    return _build_relation(sig.pvars, g[:, 0], g[:, 1], g[:, 2], ok, total,
                           cap), total


def _inl_ranges(ds, prim: int, sec: int, qhi, qlo, valid):
    """Probe one source's key planes -> (starts, lens), all pids batched.

    The sorted permutation's key planes are simply two columns of its
    device-resident rows (core/index.py::key_cols), so the rows matching
    (pid, key) form a composite-key range — start at (pid, key), end at
    (pid, key + 1).  ``qhi``/``qlo``/``valid`` carry ALL pid groups
    concatenated (k probes per pid), so one source costs exactly two
    pair-search launches regardless of how many pids are probed.
    Invalid probe rows get zero-length ranges.  The search reads the
    table in place, so any table size probes the same way.
    """
    t_hi, t_lo = ds[:, prim], ds[:, sec]
    starts = ops.pair_search(t_hi, t_lo, qhi, qlo)
    ends = ops.pair_search(t_hi, t_lo, qhi, qlo + 1)
    lens = jnp.where(valid, jnp.maximum(ends - starts, 0), 0)
    return starts, lens


def _eval_inl(sig: PatternSig, cap: int, stores, dyn, rel: Relation):
    """Index-nested-loop join: probe a sorted store with the current relation.

    Returns (joined Relation, match count) — the count is the expanded hit
    total before capacity clipping, the INL analogue of ``_eval_pattern``'s
    per-pattern total (EXPLAIN reads both through the executable).

    The Q4-style fallback: when the accumulated relation is tiny next to a
    pattern's row count, evaluating the pattern in full (a huge slice or
    scan) just to sort-merge-join it away is wasted work.  Instead, each
    bound value of the shared variable probes the pattern's composite-key
    permutation (PSO for a subject probe, POS for an object probe) with the
    pair-search kernel; the hit ranges expand through one segment mapping,
    and every output row carries its probe row's bindings plus the
    pattern's newly bound columns.  Both view sources are probed (delta
    ranges offset by the base row count) and tombstones filter through the
    gathered liveness bits — semantics identical to eval-then-join.
    """
    ds = stores[sig.store]
    prim, sec = key_cols(sig.store)
    var = sig.pvars[sig.probe_pos]
    probe = rel.col(var)
    k = probe.shape[0]
    pid_arr = dyn["pid"]  # int32[n_pids] — distinct store ids in the interval
    qlo1 = jnp.where(rel.valid, probe, 0)  # avoid key+1 overflow on INVALID
    base_n = ds.base.shape[0]
    # one probe batch per pid, concatenated: [pid0 x k, pid1 x k, ...] —
    # a source then costs two pair-search launches total (not per pid)
    valid = jnp.tile(rel.valid, sig.n_pids)
    qlo = jnp.tile(qlo1, sig.n_pids)
    qhi = jnp.where(valid, jnp.repeat(pid_arr, k), INVALID)
    seg_starts, seg_lens = [], []
    for src_rows, offset in (((ds.base, 0),) if ds.delta is None
                             else ((ds.base, 0), (ds.delta, base_n))):
        st, ln = _inl_ranges(src_rows, prim, sec, qhi, qlo, valid)
        seg_starts.append(st + offset)
        seg_lens.append(ln)
    starts = jnp.concatenate(seg_starts)
    lens = jnp.concatenate(seg_lens)
    src, ok, total, seg = ops.segment_positions(starts, lens, cap)
    rows = ops.two_source_gather(ds.base, ds.delta, src)
    alive = ops.two_source_gather(ds.base_alive, ds.delta_alive, src)
    ok = ok & alive
    probe_row = jnp.mod(seg, k)  # every segment group is one probe batch

    s, p, o = rows[:, 0], rows[:, 1], rows[:, 2]
    for posi in sig.residual:  # constant terms re-checked on the hit rows
        tsig = (sig.s_sig, sig.p_sig, sig.o_sig)[posi]
        key = ("s", "p", "o")[posi]
        ok = ok & _term_mask_dyn((s, p, o)[posi], tsig, dyn[key])

    carried = rel.cols[:, probe_row]  # probe bindings ride along
    out_vars = list(rel.vars)
    out_cols = [carried[i] for i in range(len(rel.vars))]
    seen = dict(zip(rel.vars, out_cols))
    for v, colv in zip(sig.pvars, (s, p, o)):
        if v is None:
            continue
        if v in seen:  # shared var: probe key (equal by construction) or
            ok = ok & (seen[v] == colv)  # a repeated var inside the pattern
            continue
        seen[v] = colv
        out_vars.append(v)
        out_cols.append(colv)
    out_cols = [jnp.where(ok, c, INVALID) for c in out_cols]
    return Relation(
        vars=tuple(out_vars),
        cols=jnp.stack(out_cols),
        valid=ok,
        overflow=rel.overflow + jnp.maximum(total - cap, 0),
    ), total


def scan_relation(spo, pattern_vars, pat_terms, mode: str, cap: int, extra=None):
    """Filter the store and compact matching rows into a Relation.

    Standalone oracle entry point (the engine lowers patterns once and runs
    them through cached executables instead).
    """
    from repro.core.delta import DevStore

    sig, dyn = _lower_scan(pattern_vars, pat_terms, extra, mode)
    stores = {"scan": DevStore(
        base=spo,
        base_alive=jnp.ones(spo.shape[0], dtype=bool),
        delta=None,
        delta_alive=None,
    )}
    rel, total = _eval_pattern(sig, cap, stores, dyn)
    return rel, total


def _lower_scan(pvars, terms, extra, mode: str):
    """Lower one pattern to a scan signature + traced constants."""
    s_sig, s_dyn = _lower_term(terms[0])
    p_sig, p_dyn = _lower_term(terms[1])
    o_sig, o_dyn = _lower_term(terms[2])
    dyn = {}
    if s_dyn is not None:
        dyn["s"] = s_dyn
    if p_dyn is not None:
        dyn["p"] = p_dyn
    if o_dyn is not None:
        dyn["o"] = o_dyn
    if extra is not None:
        tid, dom, rng = extra
        dom_cap, dom_arr = _pad_set(dom)
        rng_cap, rng_arr = _pad_set(rng)
        dyn.update(tid=jnp.int32(tid), dom=dom_arr, rng=rng_arr)
        return PatternSig(
            pvars=pvars, strategy="scan", o_sig=o_sig,
            extra_caps=(dom_cap, rng_cap, bool(len(dom)), bool(len(rng))),
        ), dyn
    # litemat/full stores are compacted (no INVALID rows), so pure-interval
    # predicates on p/o can fuse into the compaction kernel's one pass
    fused = (
        mode in ("litemat", "full")
        and s_sig is None
        and (p_sig is None or (p_sig.kind == "interval" and p_sig.n_spills == 0))
        and (o_sig is None or (o_sig.kind == "interval" and o_sig.n_spills == 0))
    )
    return PatternSig(pvars=pvars, strategy="scan", s_sig=s_sig, p_sig=p_sig,
                      o_sig=o_sig, fused=fused), dyn


def _shared_vars(a_vars, b_vars) -> list:
    """Variables two relations share, in the build side's order."""
    return [v for v in a_vars if v in b_vars]


def _join_keys(shared, a_sorted: bool) -> list:
    """The shared vars ``join`` sorts and searches on: the first two when
    two or more are shared and it sorts the build side itself, else the
    first alone."""
    return shared[:2] if len(shared) >= 2 and not a_sorted else shared[:1]


def join(a: Relation, b: Relation, cap: int, a_sorted: bool = False) -> Relation:
    """Sort-merge equi-join on all shared vars.

    With one shared var it is the sort and search key.  With two or more
    the build side is sorted on the first two at once and each probe row
    finds its match range with a pair search, so a cyclic pattern expands
    only true matches on those two (a third and later var is verified on
    the expanded rows) and ``total`` is the join's real size.

    ``a_sorted=True`` asserts the build side already sits in ascending
    ``shared[0]`` order with invalid rows last (the shard combine produces
    exactly that via the device merge), skipping the argsort; such a join
    keys on ``shared[0]`` alone and verifies every later shared var.
    """
    shared = _shared_vars(a.vars, b.vars)
    if not shared:
        raise ValueError("cartesian products not supported — reorder the plan")
    keys = _join_keys(shared, a_sorted)

    # sort build side (a) by its keys; invalid rows sink
    ka = [jnp.where(a.valid, a.col(v), INVALID) for v in keys]
    if a_sorted:
        a_cols, ka_s = a.cols, ka
    else:
        # unstable: rows of equal keys may land in any order, which moves
        # rows within the join output but never changes its set
        *ka_s, aperm = lax.sort((*ka, lax.iota(jnp.int32, ka[0].shape[0])),
                                num_keys=len(keys), is_stable=False)
        a_cols = a.cols[:, aperm]

    kb = [jnp.where(b.valid, b.col(v), INVALID) for v in keys]
    if len(keys) == 1:
        L = jnp.searchsorted(ka_s[0], kb[0], side="left")
        R = jnp.searchsorted(ka_s[0], kb[0], side="right")
    else:  # lax.sort's order on two keys is pair_less's signed (hi, lo)
        L = pair64.searchsorted_pair(*ka_s, *kb, side="left")
        R = pair64.searchsorted_pair(*ka_s, *kb, side="right")
    counts = jnp.where(b.valid & (kb[0] != INVALID), R - L, 0)
    offsets = jnp.cumsum(counts)
    total = offsets[-1]
    starts = offsets - counts

    # expand: output slot -> (probe row, match rank)
    out_idx = jnp.arange(cap, dtype=jnp.int32)
    probe = jnp.searchsorted(offsets, out_idx, side="right")
    probe_c = jnp.clip(probe, 0, counts.shape[0] - 1)
    rank = out_idx - starts[probe_c]
    build_row = jnp.clip(L[probe_c] + rank, 0, ka_s[0].shape[0] - 1)
    ok = out_idx < jnp.minimum(total, cap)

    # verify the shared vars the keys left out
    a_g = a_cols[:, build_row]
    b_g = b.cols[:, probe_c]
    for v in shared[len(keys):]:
        ok = ok & (a_g[a.vars.index(v)] == b_g[b.vars.index(v)])

    out_vars = tuple(a.vars) + tuple(v for v in b.vars if v not in a.vars)
    rows = [jnp.where(ok, a_g[i], INVALID) for i in range(len(a.vars))]
    for j, v in enumerate(b.vars):
        if v not in a.vars:
            rows.append(jnp.where(ok, b_g[j], INVALID))
    overflow = jnp.maximum(total - cap, 0) + a.overflow + b.overflow
    return Relation(vars=out_vars, cols=jnp.stack(rows), valid=ok, overflow=overflow)


def distinct(rel: Relation, select: tuple, cap: int) -> Relation:
    """Project onto ``select`` vars and deduplicate rows."""
    cols = [jnp.where(rel.valid, rel.col(v), INVALID) for v in select]
    # every selected column is a key: equal rows are identical, so the
    # unstable sort yields the lexsort's arrays
    cols = lax.sort(tuple(cols), num_keys=len(cols), is_stable=False)
    valid = cols[0] != INVALID
    neq = jnp.zeros(valid.shape[0] - 1, dtype=bool)
    for c in cols:
        neq = neq | (c[1:] != c[:-1])
    first = jnp.concatenate([jnp.ones((1,), bool), neq])
    keep = first & valid
    take, ok, n = ops.compact_indices(keep, cap)
    out = jnp.stack([jnp.where(ok, c[take], INVALID) for c in cols])
    return Relation(
        vars=select, cols=out, valid=ok,
        overflow=rel.overflow + jnp.maximum(n - cap, 0),
    )


# ---------------------------------------------------------------------------
# The engine: host-side resolution + planning, device execution
# ---------------------------------------------------------------------------


@dataclass
class QueryEngine:
    kb: EncodedKB
    spo: jnp.ndarray  # the store to query (lite / full / original)
    mode: str = "litemat"  # litemat | full | rewrite
    dtb: DeviceTBox | None = None
    slack: float = 1.5
    use_index: bool = True  # resolve eligible patterns via sorted indexes
    use_inl: bool = True  # index-nested-loop joins when one side is tiny
    inl_factor: int = 8  # pattern must outweigh the probe side by this much
    inl_max_probe: int = 4096  # never INL above this probe-side estimate
    view: StoreView | None = None  # live base+delta view (None: static store)
    _exec_cache: dict = field(default_factory=dict, repr=False)
    cache_stats: dict = field(default_factory=lambda: {"hits": 0, "misses": 0},
                              repr=False)
    # (PatternSig, probe-constant bucket) -> last observed selectivity
    # (observed rows / store rows); filled by every successful run/explain,
    # read by planner consumers.  The bucket is the tuple of
    # ``_pattern_const_key`` snapshots of every pattern up to and including
    # this one in plan order — the probe side's provenance — so two probe
    # sides sharing one signature (Q3's Professors, Q4's Chairs) never
    # alias each other's observation.
    observed_selectivity: dict = field(default_factory=dict, repr=False)

    def __post_init__(self):
        if self.dtb is None and self.kb.tbox is not None:
            self.dtb = DeviceTBox.build(self.kb.tbox)
        if self.view is None:
            self.view = StoreView.static(self.spo)

    def set_view(self, view: StoreView) -> None:
        """Swap in a fresh store view after a mutation.

        The plan cache survives: executables are keyed on signatures and
        capacity buckets, and jit re-specializes on the new store shapes
        only where they actually changed (delta buckets are powers of two
        precisely to keep that rare).
        """
        self.view = view
        self.spo = view.base_rows

    @property
    def index(self) -> StoreIndex:
        """Sorted permutations of this engine's base store."""
        return self.view.base_index

    # -- constant resolution (context-aware, paper §III intro) --------------
    def _resolve(self, term, position: str, type_pattern: bool) -> Term:
        tbox = self.kb.tbox
        if isinstance(term, (int, np.integer)):
            return Term(lo=int(term), hi=int(term) + 1)
        name = term
        if position == "p" and tbox is not None:
            enc = tbox.properties
        elif position == "o" and type_pattern and tbox is not None:
            enc = tbox.concepts
        else:
            enc = None
        if enc is not None and (name in enc.name_to_id or name in enc.tax.merged):
            if self.mode == "rewrite":
                return Term(lo=0, hi=0, members=np.sort(np.array(enc.subsumees(name), dtype=np.int32)))
            if self.mode == "full":
                i = enc.id_of(name)
                return Term(lo=i, hi=i + 1)
            (lo, hi), spills = enc.interval_of(name)
            return Term(lo=lo, hi=hi, spills=tuple(spills))
        ids = self.kb.locate([name])
        if ids[0] < 0:
            raise KeyError(f"unknown term {name!r}")
        return Term(lo=int(ids[0]), hi=int(ids[0]) + 1)

    def _prepare(self, patterns):
        """Resolve constants; attach rewrite extras for type patterns."""
        prepared = []
        for pat in patterns:
            p_is_const = not is_var(pat.p)
            type_pat = p_is_const and self.kb.tbox is not None and (
                pat.p in ("rdf:type", "a") or pat.p == self.kb.tbox.rdf_type_id
            )
            terms = (
                None if is_var(pat.s) else self._resolve(pat.s, "s", False),
                None if is_var(pat.p) else self._resolve(pat.p, "p", type_pat),
                None if is_var(pat.o) else self._resolve(pat.o, "o", type_pat),
            )
            pvars = tuple(t if is_var(t) else None for t in (pat.s, pat.p, pat.o))
            extra = None
            if self.mode == "rewrite" and type_pat and terms[2] is not None and is_var(pat.s):
                extra = self._rewrite_extra(terms[2])
            prepared.append((pvars, terms, extra))
        return prepared

    def _rewrite_extra(self, o_term: Term):
        """Property sets whose (effective) domain/range entails the target."""
        tbox = self.kb.tbox
        targets = set(o_term.members.tolist())
        dom_set, rng_set = [], []
        dr_ids = np.asarray(self.dtb.dr_prop_ids)
        dom_tbl = np.asarray(self.dtb.domain_table)
        rng_tbl = np.asarray(self.dtb.range_table)
        penc = tbox.properties
        for i, pid in enumerate(dr_ids.tolist()):
            if pid < 0:
                continue
            doms = [v for v in dom_tbl[i].tolist() if v >= 0]
            rngs = [v for v in rng_tbl[i].tolist() if v >= 0]
            subs = penc.subsumees(penc.name_of(pid))  # sub-properties inherit
            if any(d in targets for d in doms):
                dom_set.extend(subs)
            if any(r in targets for r in rngs):
                rng_set.extend(subs)
        return (
            int(tbox.rdf_type_id),
            np.sort(np.unique(np.array(dom_set, dtype=np.int32))),
            np.sort(np.unique(np.array(rng_set, dtype=np.int32))),
        )

    # -- pattern lowering: strategy choice + cardinality ---------------------
    def _lower(self, pvars, terms, extra):
        """-> (PatternSig, dyn pytree, host count or None).

        ``count`` is exact* and free (range lengths) for slice patterns
        (*an upper bound when tombstones sit inside a range); scan patterns
        report None and are counted by one cached device pass.
        """
        s_t, p_t, o_t = terms
        indexable = (
            self.use_index
            and extra is None
            and self.mode in ("litemat", "full")
            and all(t is None or t.members is None for t in terms)
        )
        if indexable and p_t is not None:
            view = self.view
            # effective predicate id: exact single-width interval, or a wide
            # interval whose store run holds only one distinct predicate
            # (the common rdf:type case) — both collapse to composite ranges
            pid = p_t.lo if (p_t.hi == p_t.lo + 1 and not p_t.spills) else None
            if pid is None and not p_t.spills:
                pid = view.single_p_run(p_t.lo, p_t.hi)
            ranges = None
            store = "pos"
            residual = ()
            o_sig = o_dyn = None
            if s_t is None and o_t is None:
                ranges = [r for a, b in p_t.intervals()
                          for r in view.p_ranges(a, b)]
            elif s_t is None and o_t is not None:
                if pid is not None:
                    ranges = [r for a, b in o_t.intervals()
                              for r in view.po_ranges(pid, a, b)]
                else:  # mixed p run sliced, o re-checked on the gathered rows
                    ranges = [r for a, b in p_t.intervals()
                              for r in view.p_ranges(a, b)]
                    residual = (2,)
                    o_sig, o_dyn = _lower_term(o_t)
            elif s_t is not None and pid is not None:
                ranges = [r for a, b in s_t.intervals()
                          for r in view.ps_ranges(pid, a, b)]
                store = "pso"
                if o_t is not None:  # o re-checked on the gathered rows
                    residual = (2,)
                    o_sig, o_dyn = _lower_term(o_t)
            if ranges is not None:
                return self._slice_plan(pvars, ranges, store, residual,
                                        o_sig=o_sig, o_dyn=o_dyn)
        if indexable and p_t is None and (s_t is not None or o_t is not None):
            # variable predicate: SPO (constant subject) / OSP (constant
            # object) permutations keep these off the full-scan path
            view = self.view
            if s_t is not None:
                ranges = [r for a, b in s_t.intervals()
                          for r in view.s_ranges(a, b)]
                store = "spo"
                residual, o_sig, o_dyn = (), None, None
                if o_t is not None:  # (s ?p o): o re-checked after the gather
                    residual = (2,)
                    o_sig, o_dyn = _lower_term(o_t)
                return self._slice_plan(pvars, ranges, store, residual,
                                        o_sig=o_sig, o_dyn=o_dyn)
            ranges = [r for a, b in o_t.intervals()
                      for r in view.o_ranges(a, b)]
            return self._slice_plan(pvars, ranges, "osp", ())
        sig, dyn = _lower_scan(pvars, terms, extra, self.mode)
        return sig, dyn, None

    @staticmethod
    def _slice_plan(pvars, ranges, store, residual, o_sig=None, o_dyn=None):
        lens = [max(r1 - r0, 0) for r0, r1 in ranges]
        sig = PatternSig(pvars=pvars, strategy="slice", store=store,
                         k=len(ranges), o_sig=o_sig, residual=residual)
        dyn = {
            "starts": jnp.asarray([r0 for r0, _ in ranges], jnp.int32),
            "lens": jnp.asarray(lens, jnp.int32),
        }
        if o_dyn is not None:
            dyn["o"] = o_dyn
        return sig, dyn, sum(lens)

    def _pattern_count(self, sig: PatternSig, dyn) -> int:
        """Planning cardinality of a scan pattern (cached jitted reduction):
        one device round trip, counted in ``query/plan_syncs``."""
        if self.view.n == 0:  # empty store (e.g. a fresh shard): no device pass
            return 0
        key = ("count", sig)
        fn = self._exec_cache.get(key)
        if fn is None:
            def plan_count(ds, d, _sig=sig):
                sources = [(ds.base, ds.base_alive)]
                if ds.delta is not None:
                    sources.append((ds.delta, ds.delta_alive))
                total = jnp.int32(0)
                for spo, alive in sources:
                    if _sig.extra_caps is not None:
                        # a row can bind through BOTH branches: count both
                        ms, mo = _type_rewrite_masks_dyn(
                            spo, alive, d["o"], d["tid"], d["dom"],
                            d["rng"], _sig.extra_caps[2], _sig.extra_caps[3])
                        total += ms.astype(jnp.int32).sum()
                        if mo is not None:
                            total += mo.astype(jnp.int32).sum()
                    else:
                        m, _ = _scan_mask(_sig, spo, alive, d)
                        total += m.astype(jnp.int32).sum()
                return total
            fn = jax.jit(plan_count)
            self._exec_cache[key] = fn
        REGISTRY.counter("query/plan_syncs").inc()
        with obs_trace.span("query.plan.count", strategy=sig.strategy):
            return int(fn(self.view.dev("scan"), dyn))

    @staticmethod
    def _make_run_device(sigs, caps, join_cap: int, select):
        """Build the device-side plan body shared by the solo and batched
        executables.

        The function returns (cols, valid, overflow, totals): ``totals``
        is int32[n_patterns] — each pattern's OBSERVED match count before
        capacity clipping, in plan order — computed inside the same trace
        (no extra device pass; the scalars ride the overflow fetch).
        EXPLAIN and the selectivity capture read their observed-vs-estimated
        row counts off it.
        """

        def query_exec(stores, dyns):
            rel = None
            totals = []
            for sig, cap, dyn in zip(sigs, caps, dyns):
                if sig.strategy == "inl":  # consumes the running relation
                    rel, t = _eval_inl(sig, cap, stores, dyn, rel)
                else:
                    r, t = _eval_pattern(sig, cap, stores, dyn)
                    rel = r if rel is None else join(rel, r, join_cap)
                totals.append(t)
            out = distinct(rel, select, join_cap)
            return (out.cols, out.valid, out.overflow,
                    jnp.stack(totals).astype(jnp.int32))

        return query_exec

    @staticmethod
    def _composite_joins(sigs) -> int:
        """How many of the plan's sort-merge joins key on two shared vars.

        Walks the relation's vars the way ``query_exec`` builds them: an
        INL step or a join appends the pattern's new vars in order.
        """
        n, rel_vars = 0, None
        for sig in sigs:
            pv = tuple(dict.fromkeys(v for v in sig.pvars if v is not None))
            if rel_vars is None:
                rel_vars = pv
                continue
            if (sig.strategy != "inl"
                    and len(_join_keys(_shared_vars(rel_vars, pv),
                                       False)) == 2):
                n += 1
            rel_vars += tuple(v for v in pv if v not in rel_vars)
        return n

    @staticmethod
    def _timed_compile(fn, label: str):
        """Wrap a fresh jitted plan so its FIRST call — the one that pays
        trace+compile — is timed into ``query/compile_seconds{sig=}``.

        jax.jit compiles lazily, so the only honest place to measure is
        the first dispatch; ``block_until_ready`` there folds device
        execution into the sample, but compile dominates by orders of
        magnitude and the sync happens exactly once per executable.
        """
        state = {"pending": True}

        @wraps(fn)
        def wrapper(*args):
            if not state["pending"]:
                return fn(*args)
            state["pending"] = False
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            REGISTRY.histogram("query/compile_seconds", sig=label).observe(
                time.perf_counter() - t0)
            return out

        return wrapper

    def _memo(self, key, slabel: str, kind: str, make):
        """Plan-cache lookup; ``make()`` builds the jitted function on a
        miss.  Atomic across threads, so concurrent planners of one key
        share one executable (jit compiles on first call, not here)."""
        with _EXEC_LOCK:
            fn = self._exec_cache.get(key)
            hit = fn is not None
            if not hit:
                fn = self._timed_compile(jax.jit(make()), slabel)
                self._exec_cache[key] = fn
            self.cache_stats["hits" if hit else "misses"] += 1
        event = ("hit" if hit else "miss") + ("_batch" if kind == "batch"
                                               else "")
        REGISTRY.counter("query/plan_cache", event=event, sig=slabel).inc()
        return fn

    def _executable(self, key, sigs, caps, join_cap: int, select):
        """Memoized jitted plan: signature + buckets -> compiled function."""
        return self._memo(key, sig_label(sigs), "solo",
                          lambda: self._make_run_device(sigs, caps, join_cap,
                                                        select))

    def _batch_executable(self, key, sigs, caps, join_cap: int, select):
        """Memoized VMAPPED plan: one dispatch answers a whole request batch.

        The stores axis is shared (all batch members execute against the
        same pinned view); the dyn-constant pytree carries a leading batch
        axis.  Every kernel in the plan body (stream compaction, merge
        path, pair search) lifts through ``jax.vmap``, so a batch of B
        same-signature requests costs ONE XLA dispatch instead of B.
        """
        def make():
            batched = jax.vmap(self._make_run_device(sigs, caps, join_cap,
                                                     select),
                               in_axes=(None, 0))

            def query_exec_batch(stores, dyns):
                return batched(stores, dyns)

            return query_exec_batch

        return self._memo(key, sig_label(sigs), "batch", make)

    @staticmethod
    def _bucket(n: int) -> int:
        return _pow2(n, floor=256)

    @staticmethod
    def _plan_order(prepared, counts):
        """Greedy join order: smallest first, stay connected when possible."""
        remaining = list(range(len(prepared)))
        remaining.sort(key=lambda i: counts[i])
        order = [remaining.pop(0)]
        bound_vars = set(v for v in prepared[order[0]][0] if v)
        while remaining:
            connected = [i for i in remaining if bound_vars & {v for v in prepared[i][0] if v}]
            pick = min(connected or remaining, key=lambda i: counts[i])
            remaining.remove(pick)
            order.append(pick)
            bound_vars |= {v for v in prepared[pick][0] if v}
        return order

    def _stores(self, sigs):
        """DevStores the executable takes as inputs, keyed per signature.

        Each key resolves through the view's device cache: the base arrays
        are the resident index copies and only the O(delta) bucket (plus
        any tombstone scatters) moves per mutation.
        """
        v = self.view
        stores = {}
        if any(sig.strategy == "scan" for sig in sigs):
            stores["scan"] = v.dev("scan")
        for perm in {sig.store for sig in sigs
                     if sig.strategy in ("slice", "inl")}:
            stores[perm] = v.dev(perm)
        return stores

    def _inl_pids(self, p_t: Term, limit: int = 4):
        """Distinct store predicate ids of a constant p term, or None.

        A LiteMat property interval usually covers a handful of store ids
        (the property and its sub-properties); each becomes one composite-
        key probe group.  None (too many / spilled) leaves the pattern on
        its slice or scan strategy.
        """
        if p_t.spills:
            return None
        if p_t.hi == p_t.lo + 1:
            return [p_t.lo]
        return self.view.distinct_p_ids(p_t.lo, p_t.hi, limit)

    def _apply_inl(self, prepared, lowered, counts, order, ckeys):
        """Convert eligible joins to index-nested-loop probes (in place).

        Walking the join order with a running probe-side estimate (the
        smallest relation seen so far — the greedy order starts tiny), a
        later pattern whose row count dwarfs that estimate is re-lowered
        from evaluate-then-merge-join to an INL probe of its composite-key
        permutation (PSO when the shared variable is the subject, POS when
        it is the object) — the Q4 shape: a huge (?x worksFor ?y) pattern
        probed by a handful of Chairs instead of materialized and sorted.
        Its planning count drops to the probe-side estimate times a fanout
        allowance, shrinking every downstream capacity (overflow retries
        still protect underestimates).

        Once a candidate probe shape has actually executed, its OBSERVED
        output row count (``observed_selectivity``, keyed by the INL
        PatternSig PLUS the probe-constant bucket — the const keys of
        every pattern walked so far, i.e. this probe side's provenance)
        feeds back into the call and then DECIDES alone: a pattern whose
        probe-side ESTIMATE was too big for the heuristic still converts
        when the observed INL output times ``inl_factor`` undercuts the
        merge-side row count, and a pattern the heuristic would have
        converted is VETOED when the observation says the probe fans out
        past the merge-side cost.  The bucket keying is what makes the
        veto safe: Q3's Professors and Q4's Chairs lower to the same
        worksFor signature but carry different upstream constants, so
        neither's observation can ever speak for the other.  Capacity is
        sized with a 2x margin over both the observation and the probe
        estimate; overflow retries protect the rest.
        """
        indexable = (self.use_inl and self.use_index
                     and self.mode in ("litemat", "full"))
        if not indexable or len(order) < 2:
            return
        store_n = max(self.view.n, 1)
        bound = {v for v in prepared[order[0]][0] if v}
        est = counts[order[0]]
        ctx = [ckeys[order[0]]]  # probe provenance: const keys walked so far
        for i in order[1:]:
            pvars, terms, extra = prepared[i]
            pat_vars = {v for v in pvars if v}
            heuristic = counts[i] >= self.inl_factor * max(est, 1)
            # candidate construction costs a distinct-pid probe, so only
            # pay it when the heuristic already says INL or when prior
            # observations exist that could overturn it
            eligible = (
                extra is None
                and est <= self.inl_max_probe
                and terms[1] is not None
                and all(t is None or t.members is None for t in terms)
                and (heuristic or bool(self.observed_selectivity))
            )
            if eligible:
                pids = self._inl_pids(terms[1])
                probe_pos = store = None
                if pids:
                    if pvars[0] is not None and pvars[0] in bound:
                        probe_pos, store = 0, "pso"
                        res_t, res_pos = terms[2], 2
                    elif pvars[2] is not None and pvars[2] in bound:
                        probe_pos, store = 2, "pos"
                        res_t, res_pos = terms[0], 0
                if probe_pos is not None:
                    dyn = {"pid": jnp.asarray(
                        np.asarray([_clip32(p) for p in pids], np.int32))}
                    residual = ()
                    r_sig = None
                    if res_t is not None:
                        r_sig, r_dyn = _lower_term(res_t)
                        residual = (res_pos,)
                        dyn[("s", "p", "o")[res_pos]] = r_dyn
                    sig = PatternSig(
                        pvars=pvars, strategy="inl", store=store,
                        probe_pos=probe_pos, residual=residual,
                        n_pids=len(pids),
                        s_sig=r_sig if res_pos == 0 else None,
                        o_sig=r_sig if res_pos == 2 else None,
                    )
                    bucket = tuple(ctx) + (ckeys[i],)
                    obs = self.observed_selectivity.get((sig, bucket))
                    if obs is not None:
                        # bucketed observation: it speaks for exactly this
                        # probe side, so it decides alone — including the
                        # veto of a heuristic-approved conversion
                        inl_rows = max(int(round(obs * store_n)), 1)
                        convert = inl_rows * self.inl_factor <= counts[i]
                        sized = max(inl_rows * 2, max(est, 1) * 2)
                    else:
                        convert = heuristic
                        sized = max(est, 1) * 32
                    if convert:
                        counts[i] = min(counts[i], sized)
                        lowered[i] = (sig, dyn, counts[i])
            bound |= pat_vars
            ctx.append(ckeys[i])
            est = min(est, counts[i])

    def _plan(self, patterns, select):
        """Host planning: -> (sigs, dyns, ordered caps, join_cap, sel,
        stores, order, est, buckets).

        The first six elements are the PR-5 contract (core/shard.py indexes
        them positionally); ``order`` maps plan position -> original pattern
        index, ``est`` carries the planner's per-pattern cardinality
        estimates in plan order (what EXPLAIN compares observed counts to),
        and ``buckets`` the per-pattern probe-constant buckets in plan
        order — pattern j's bucket is the const keys of plan positions
        0..j, the key half that de-aliases ``observed_selectivity``.
        """
        with obs_trace.span("query.plan", mode=self.mode,
                            n_patterns=len(patterns)):
            prepared = self._prepare(patterns)
            lowered = [self._lower(*pre) for pre in prepared]
            counts = [
                c if c is not None else self._pattern_count(sig, dyn)
                for sig, dyn, c in lowered
            ]
            ckeys = [_pattern_const_key(pre[1]) for pre in prepared]
            order = self._plan_order(prepared, counts)
            self._apply_inl(prepared, lowered, counts, order, ckeys)
            caps = [self._bucket(int(counts[i] * self.slack) + 16)
                    for i in order]
            join_cap = self._bucket(int(max(counts) * self.slack) + 16)

            sigs = tuple(lowered[i][0] for i in order)
            dyns = tuple(lowered[i][1] for i in order)
            all_vars = tuple(dict.fromkeys(
                v for sig in sigs for v in sig.pvars if v is not None))
            sel = tuple(select) if select else all_vars
            buckets = tuple(tuple(ckeys[i] for i in order[: j + 1])
                            for j in range(len(order)))
            return (sigs, dyns, caps, join_cap, sel, self._stores(sigs),
                    tuple(order), tuple(counts[i] for i in order), buckets)

    def _record_observed(self, sigs, totals, buckets) -> None:
        """Land observed per-pattern selectivities for the planner.

        ``observed_selectivity`` (engine-local, keyed by ``(PatternSig,
        probe-constant bucket)``) is the exact read-back surface for the
        planner; the ``planner/selectivity`` gauge keeps the last one per
        strategy and store for operators.
        """
        store_n = max(self.view.n, 1)
        for sig, obs, bucket in zip(sigs, totals, buckets):
            obs = int(obs)
            self.observed_selectivity[(sig, bucket)] = obs / store_n
            REGISTRY.gauge("planner/selectivity", strategy=sig.strategy,
                           store=sig.store).set(obs / store_n)

    def run(self, patterns, select=None, max_retries: int = 6):
        """Execute; returns (rows int32[k, n_select], select var names)."""
        return self._run_planned(self._plan(patterns, select), max_retries)

    def _run_planned(self, planned, max_retries: int = 6):
        """Execute an already-planned query (the solo dispatch path)."""
        with obs_trace.span("query.execute"):
            sigs, dyns, caps, join_cap, sel, stores, _, _, buckets = planned
            slabel = sig_label(sigs)
            composite = self._composite_joins(sigs)
            for attempt in range(max_retries):
                key = ("exec", self.mode, sigs, tuple(caps), join_cap, sel)
                misses0 = self.cache_stats["misses"]
                fn = self._executable(key, sigs, tuple(caps), join_cap, sel)
                if composite:
                    REGISTRY.counter("join/composite_key", site="query",
                                     sig=slabel).inc(composite)
                t0 = time.perf_counter()
                cached = self.cache_stats["misses"] == misses0
                with obs_trace.span("query.dispatch", cached=cached,
                                    join_cap=join_cap,
                                    composite_joins=composite) as dsp:
                    cols, valid, overflow, totals = fn(stores, dyns)
                with obs_trace.span("query.device_wait"):
                    done = int(overflow) == 0
                REGISTRY.histogram("query/exec_seconds",
                                   sig=slabel).observe(
                    time.perf_counter() - t0)
                dsp.set_attr(overflow=not done)
                if done:
                    if attempt:
                        REGISTRY.histogram("join/capacity_depth",
                                           site="query", sig=slabel,
                                           shard="local").observe(attempt)
                    with obs_trace.span("query.fetch") as fetch:
                        self._record_observed(sigs, np.asarray(totals),
                                              buckets)
                        n = int(valid.sum())
                        rows = np.asarray(cols)[:, :n].T
                        fetch.set_attr(rows=n)
                    return rows, sel
                dsp.add_event("overflow_retry", attempt=attempt,
                              join_cap=join_cap)
                REGISTRY.counter("join/capacity_retry", site="query",
                                 sig=slabel, shard="local").inc()
                join_cap *= 2
                caps = [c * 2 for c in caps]
            raise RuntimeError("query kept overflowing its capacity buckets")

    # -- micro-batched execution (ROADMAP item 1) ---------------------------
    def _batch_caps(self, planned_group):
        """Unified capacity buckets for a same-signature batch.

        Member caps start at the elementwise max (the shared executable
        must hold the largest member), then observed selectivities —
        looked up per member by ``(sig, probe-constant bucket)`` — adjust
        them.  When EVERY member of the batch has been observed, the cap
        becomes the largest member's observed floor, which may SHRINK an
        over-provisioned planner estimate (the bucketed keying makes that
        safe: each member's floor speaks for exactly its own constants).
        While any member is still unobserved, observations only grow the
        cap — shrinking on partial evidence would trade the unobserved
        member's overflow retry for the whole batch's.
        """
        sigs = planned_group[0][0]
        caps = [max(p[2][j] for p in planned_group)
                for j in range(len(sigs))]
        join_cap = max(p[3] for p in planned_group)
        store_n = max(self.view.n, 1)
        for j, sig in enumerate(sigs):
            obs = [self.observed_selectivity.get((sig, p[8][j]))
                   for p in planned_group]
            known = [o for o in obs if o is not None]
            if not known:
                continue
            floor = max(self._bucket(int(o * store_n * self.slack) + 16)
                        for o in known)
            if len(known) == len(obs):
                caps[j] = floor  # complete evidence: shrink allowed
            else:
                caps[j] = max(caps[j], floor)
        return caps, max(join_cap, max(caps))

    def run_batch(self, requests, max_retries: int = 6):
        """Execute a batch of (patterns, select) requests in shared
        dispatches; returns [(rows, sel), ...] aligned with ``requests``.

        The batcher's engine half: every request is planned individually,
        structurally identical requests are answered ONCE and fanned out,
        and distinct requests whose patterns lower to the same signature
        tuple (projecting the same variables) execute as one vmapped
        dispatch over batch-stacked dyn constants — capacities unified by
        :meth:`_batch_caps` and the batch axis padded to a power of two so
        nearby batch sizes reuse one compiled executable.  Requests whose
        signatures match nobody else's fall back to the solo path; every
        member still lands its own observed-selectivity sample.
        """
        results = [None] * len(requests)
        uniq_keys, uniq = {}, []  # structural dedupe: answer once, fan out
        for i, (pats, select) in enumerate(requests):
            k = (tuple((p.s, p.p, p.o) for p in pats),
                 tuple(select) if select is not None else None)
            j = uniq_keys.get(k)
            if j is None:
                uniq_keys[k] = len(uniq)
                uniq.append((self._plan(pats, select), [i]))
            else:
                uniq[j][1].append(i)
        groups = {}
        for planned, members in uniq:
            groups.setdefault((planned[0], planned[4]), []).append(
                (planned, members))
        for (sigs, sel), entries in groups.items():
            if len(entries) == 1:
                planned, members = entries[0]
                rows, _ = self._run_planned(planned, max_retries)
                for i in members:
                    results[i] = (rows, sel)
                continue
            caps, join_cap = self._batch_caps([e[0] for e in entries])
            stores = entries[0][0][5]
            B = len(entries)
            Bp = _pow2(B, floor=2)  # pad slots repeat the last member
            dyn_list = ([e[0][1] for e in entries]
                        + [entries[-1][0][1]] * (Bp - B))
            dyn_stack = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *dyn_list)
            slabel = sig_label(sigs)
            composite = self._composite_joins(sigs)
            for attempt in range(max_retries):
                key = ("bexec", self.mode, sigs, tuple(caps), join_cap,
                       sel, Bp)
                fn = self._batch_executable(key, sigs, tuple(caps),
                                            join_cap, sel)
                if composite:
                    REGISTRY.counter("join/composite_key", site="batch",
                                     sig=slabel).inc(composite)
                t0 = time.perf_counter()
                with obs_trace.span("query.dispatch", join_cap=join_cap,
                                    batch=B,
                                    composite_joins=composite) as dsp:
                    cols, valid, overflow, totals = fn(stores, dyn_stack)
                with obs_trace.span("query.device_wait"):
                    ok = int(np.asarray(overflow)[:B].max()) == 0
                REGISTRY.histogram("query/exec_seconds", sig=slabel).observe(
                    time.perf_counter() - t0)
                if ok:
                    if attempt:
                        REGISTRY.histogram(
                            "join/capacity_depth", site="batch", sig=slabel,
                            shard="local").observe(attempt)
                    break
                dsp.add_event("overflow_retry", attempt=attempt,
                              join_cap=join_cap, batch=B)
                REGISTRY.counter("join/capacity_retry", site="batch",
                                 sig=slabel, shard="local").inc()
                join_cap *= 2
                caps = [c * 2 for c in caps]
            else:
                raise RuntimeError(
                    "batched query kept overflowing its capacity buckets")
            with obs_trace.span("query.fetch", batch=B):
                cols_h = np.asarray(cols)
                valid_h = np.asarray(valid)
                totals_h = np.asarray(totals)
            for b, (planned, members) in enumerate(entries):
                self._record_observed(sigs, totals_h[b], planned[8])
                n = int(valid_h[b].sum())
                rows = cols_h[b][:, :n].T
                for i in members:
                    results[i] = (rows, sel)
        return results

    def explain(self, patterns, select=None, execute: bool = True) -> dict:
        """EXPLAIN: per-pattern strategy, buckets, estimated-vs-observed rows.

        Plans exactly like ``run`` and (by default) executes once through
        the same cached executable to read each pattern's observed match
        count off the device — estimates vs observed is the signal the
        INL-vs-merge choice and the ROADMAP item-1 batcher need.  Observed
        selectivities land in the process registry via
        :meth:`_record_observed`.  ``execute=False`` reports the plan only.
        """
        (sigs, dyns, caps, join_cap, sel, stores,
         order, est, buckets) = self._plan(patterns, select)
        observed = [None] * len(sigs)
        n_rows = None
        hot_keys = {}
        if execute and self.view.n:
            key = ("exec", self.mode, sigs, tuple(caps), join_cap, sel)
            fn = self._executable(key, sigs, tuple(caps), join_cap, sel)
            cols, valid, overflow, totals = fn(stores, dyns)
            observed = [int(t) for t in np.asarray(totals)]
            n_rows = int(valid.sum())
            self._record_observed(sigs, observed, buckets)
            # observed hot-key skew: for every join variable we can read
            # off the result (selected + shared by >= 2 patterns), how
            # lopsided is the per-key row distribution?  This is the
            # host-visible face of the device-side capacity-retry metrics:
            # a skew near 1.0 means uniform keys; a large max/mean ratio
            # explains join/capacity_retry doublings for this signature.
            if n_rows:
                rows_h = np.asarray(cols)[:, :n_rows].T
                uses = {}
                for sig in sigs:
                    for v in sig.pvars:
                        if v is not None:
                            uses[v] = uses.get(v, 0) + 1
                for v in sel:
                    if uses.get(v, 0) < 2:
                        continue
                    _, cnt = np.unique(rows_h[:, sel.index(v)],
                                       return_counts=True)
                    top, mean = int(cnt.max()), float(cnt.mean())
                    hot_keys[v] = {
                        "max_rows_per_key": top,
                        "mean_rows_per_key": mean,
                        "skew": top / mean,
                    }
        store_n = max(self.view.n, 1)
        pats = []
        for j, sig in enumerate(sigs):
            entry = {
                "pattern_index": order[j],
                "strategy": sig.strategy,
                "store": sig.store,
                "cap": caps[j],
                "estimated_rows": int(est[j]),
                "observed_rows": observed[j],
            }
            if sig.strategy == "slice":
                entry["n_ranges"] = sig.k
            if sig.strategy == "scan":
                entry["fused"] = sig.fused
            if sig.strategy == "inl":
                entry["n_pids"] = sig.n_pids
                entry["probe_pos"] = sig.probe_pos
            if observed[j] is not None:
                entry["selectivity"] = observed[j] / store_n
            pats.append(entry)
        return {
            "mode": self.mode,
            "select": list(sel),
            "store_rows": int(self.view.n),
            "join_cap": join_cap,
            "n_result_rows": n_rows,
            "patterns": pats,
            "hot_keys": hot_keys,
        }

    def prewarm_calls(self, queries, buckets=(), selects=None) -> list:
        """One zero-argument call per query that compiles its executables.

        A call runs its query until the plan stops changing (the first
        run's observed selectivities can re-plan it, see :meth:`_warm`),
        so the requests that follow find every executable compiled.  Each
        floor in ``buckets`` adds a call that runs the natural plan once
        with its caps raised to at least that floor, covering the bucket
        sizes the store will grow into.  Calls block until done.
        ``selects`` gives each query's projection (default: all vars).
        """
        calls = []
        for i, pats in enumerate(queries):
            select = selects[i] if selects is not None else None
            calls.append(partial(self._warm, pats, select))
            if not buckets:
                continue
            sigs, dyns, caps, join_cap, sel, stores = \
                self._plan(pats, select)[:6]
            for b in sorted({self._bucket(int(b)) for b in buckets}):
                cs = tuple(max(c, b) for c in caps)
                jc = max(join_cap, b)
                fn = self._executable(("exec", self.mode, sigs, cs, jc, sel),
                                      sigs, cs, jc, sel)
                calls.append(partial(_run_blocking, fn, stores, dyns))
        return calls

    def _warm(self, patterns, select, max_plans: int = 3) -> None:
        """Run a query, re-planned after each run, until a plan repeats."""
        seen = set()
        for _ in range(max_plans):
            planned = self._plan(patterns, select)
            key = (planned[0], tuple(planned[2]), planned[3], planned[4])
            if key in seen:
                return
            seen.add(key)
            self._run_planned(planned)

    def prewarm(self, queries, buckets=(), select=None) -> int:
        """Pre-trace executables for a query set; returns #plans compiled.

        The calls of :meth:`prewarm_calls` run concurrently.  Subsequent
        ``run`` calls whose buckets land on a prewarmed combination skip
        the trace+compile cold start entirely.
        """
        before = self.cache_stats["misses"]
        run_concurrently(self.prewarm_calls(
            queries, buckets, [select] * len(queries)))
        return self.cache_stats["misses"] - before


def _run_blocking(fn, *args):
    return jax.block_until_ready(fn(*args))
