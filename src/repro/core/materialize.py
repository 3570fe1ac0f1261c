"""Lite materialization — the paper's §IV, vectorized.

Per instance, gather *candidate concepts* (explicit rdf:type objects plus
concepts implied by rdfs:domain / rdfs:range of the properties the instance
occurs with), then keep only the Most Specific Concepts: thanks to the
interval encoding, after sorting candidates a concept is redundant iff its
immediate successor (same instance) falls inside its subsumption interval —
the paper's one-pass MSC scan, here as one sort + one vectorized adjacent
compare over the whole dataset.

RDFS subtlety the paper glosses over: ``domain`` axioms of *super*-properties
also apply (rdfs7 ∘ rdfs2/3).  We fold that in by precomputing *effective*
domain/range tables per property (union over its property-DAG ancestors) on
the host — properties are few — so the device pass stays one lookup per
triple.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.tbox import TBox
from repro.utils import pair64

INVALID = jnp.int32(np.iinfo(np.int32).max)  # sorts to the end


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "concept_sorted_ids", "concept_sorted_bounds", "concept_spill_lo",
        "concept_spill_hi", "concept_ancestors", "prop_sorted_ids",
        "prop_ancestors", "dr_prop_ids", "domain_table", "range_table",
    ],
    meta_fields=["rdf_type_id"],
)
@dataclass(frozen=True)
class DeviceTBox:
    """The TBox tables the device passes need, as jnp arrays."""

    rdf_type_id: int
    concept_sorted_ids: jnp.ndarray  # int32[C]
    concept_sorted_bounds: jnp.ndarray  # int32[C]
    concept_spill_lo: jnp.ndarray  # int32[C, S]
    concept_spill_hi: jnp.ndarray
    concept_ancestors: jnp.ndarray  # int32[C, D], -1 padded (DAG ancestors)
    prop_sorted_ids: jnp.ndarray  # int32[P]
    prop_ancestors: jnp.ndarray  # int32[P, DP], -1 padded
    dr_prop_ids: jnp.ndarray  # int32[Pdr] sorted (effective tables)
    domain_table: jnp.ndarray  # int32[Pdr, Kd], -1 padded
    range_table: jnp.ndarray  # int32[Pdr, Kr], -1 padded

    @staticmethod
    def build(tbox: TBox) -> "DeviceTBox":
        c = tbox.concepts
        p = tbox.properties
        if c.total_bits > 30 or p.total_bits > 30:
            raise ValueError(
                "device path needs narrow (<=30 bit) ids; use the wide-id host path"
            )
        # effective domain/range: union over property-DAG ancestors ---------
        pid_of_node = {i: int(p.ids[i]) for i in range(p.n)}
        direct_dom = {int(k): [int(v) for v in row if v >= 0]
                      for k, row in zip(tbox.dr_prop_ids, tbox.domain_table)}
        direct_rng = {int(k): [int(v) for v in row if v >= 0]
                      for k, row in zip(tbox.dr_prop_ids, tbox.range_table)}
        eff_dom, eff_rng = {}, {}
        for node in range(p.n):
            pid = pid_of_node[node]
            chain = [node, *sorted(p.tax.dag_ancestors(node))]
            dom = sorted({d for a in chain for d in direct_dom.get(pid_of_node[a], [])})
            rng = sorted({r for a in chain for r in direct_rng.get(pid_of_node[a], [])})
            if dom:
                eff_dom[pid] = dom
            if rng:
                eff_rng[pid] = rng
        keys = sorted(set(eff_dom) | set(eff_rng))
        Kd = max(1, max((len(v) for v in eff_dom.values()), default=0))
        Kr = max(1, max((len(v) for v in eff_rng.values()), default=0))
        P = max(1, len(keys))
        dr_ids = np.full((P,), -1, dtype=np.int32)
        dom_tbl = np.full((P, Kd), -1, dtype=np.int32)
        rng_tbl = np.full((P, Kr), -1, dtype=np.int32)
        for i, k in enumerate(keys):
            dr_ids[i] = k
            for j, v in enumerate(eff_dom.get(k, [])):
                dom_tbl[i, j] = v
            for j, v in enumerate(eff_rng.get(k, [])):
                rng_tbl[i, j] = v

        return DeviceTBox(
            rdf_type_id=int(tbox.rdf_type_id),
            concept_sorted_ids=jnp.asarray(c.sorted_ids, dtype=jnp.int32),
            concept_sorted_bounds=jnp.asarray(c.sorted_bounds, dtype=jnp.int32),
            concept_spill_lo=jnp.asarray(c.sorted_spill_lo, dtype=jnp.int32),
            concept_spill_hi=jnp.asarray(c.sorted_spill_hi, dtype=jnp.int32),
            concept_ancestors=jnp.asarray(c.sorted_ancestors, dtype=jnp.int32),
            prop_sorted_ids=jnp.asarray(p.sorted_ids, dtype=jnp.int32),
            prop_ancestors=jnp.asarray(p.sorted_ancestors, dtype=jnp.int32),
            dr_prop_ids=jnp.asarray(dr_ids),
            domain_table=jnp.asarray(dom_tbl),
            range_table=jnp.asarray(rng_tbl),
        )


def concept_bounds(dtb: DeviceTBox, concept_ids):
    """bound() for concept-id arrays via the sorted TBox table.

    Unknown ids (instances/literals) get bound = id + 1 (leaf semantics).
    """
    pos = jnp.searchsorted(dtb.concept_sorted_ids, concept_ids)
    pos = jnp.clip(pos, 0, dtb.concept_sorted_ids.shape[0] - 1)
    hit = dtb.concept_sorted_ids[pos] == concept_ids
    return jnp.where(hit, dtb.concept_sorted_bounds[pos], concept_ids + 1), pos, hit


# ---------------------------------------------------------------------------
# Candidate generation + MSC
# ---------------------------------------------------------------------------


def table_columns(table, rows):
    """``table[rows]`` of a small int32[K, W] TBox table, as W 1-D columns.

    One gather per column: a single 2-D gather would come out [len(rows),
    W] in a row-major TPU layout, each row padded to 128 lanes — the
    closure's candidate arrays then need 128/W times their size in HBM.
    """
    return [table[:, k][rows] for k in range(table.shape[1])]


def candidate_types(spo, dtb: DeviceTBox):
    """(instance, concept, explicit) candidate rows, INVALID-padded.

    Row layout (static): N explicit rows, then N per domain slot (Kd), then
    N per range slot (Kr).
    """
    s, p, o = spo[:, 0], spo[:, 1], spo[:, 2]
    is_type = p == dtb.rdf_type_id

    pos = jnp.searchsorted(dtb.dr_prop_ids, p)
    pos = jnp.clip(pos, 0, dtb.dr_prop_ids.shape[0] - 1)
    p_hit = (dtb.dr_prop_ids[pos] == p) & (~is_type)
    inst = [jnp.where(is_type, s, INVALID)]
    conc = [jnp.where(is_type, o, INVALID)]
    for col, bound in ((s, table_columns(dtb.domain_table, pos)),
                       (o, table_columns(dtb.range_table, pos))):
        for c in bound:
            ok = p_hit & (c >= 0)
            inst.append(jnp.where(ok, col, INVALID))
            conc.append(jnp.where(ok, c, INVALID))
    explicit = jnp.concatenate(
        [is_type, jnp.zeros((s.shape[0] * (len(inst) - 1),), bool)])
    return jnp.concatenate(inst), jnp.concatenate(conc), explicit


def sort_rows(*cols):
    """Sort parallel int32 columns lexicographically (first column major).

    Every column is a key, so equal rows are identical and stability is
    moot: the unstable sort gives the same arrays as ``jnp.lexsort`` and
    compiles in about half the time on a TPU.
    """
    return lax.sort(tuple(cols), num_keys=len(cols), is_stable=False)


def msc_select(inst, conc, explicit, dtb: DeviceTBox):
    """One-pass MSC over (instance, concept) candidates.

    Returns (inst_s, conc_s, keep, uniq_explicit, dropped_explicit,
    added_implicit) — all aligned to the sorted candidate order.
    """
    # sort by (instance, concept, explicit-first) so duplicate heads carry
    # explicitness; INVALID rows sink to the end.
    inst_s, conc_s, implicit_s = sort_rows(
        inst, conc, (~explicit).astype(jnp.int32))
    expl_s = implicit_s == 0
    valid = inst_s != INVALID

    first = jnp.concatenate(
        [jnp.ones((1,), bool), (inst_s[1:] != inst_s[:-1]) | (conc_s[1:] != conc_s[:-1])]
    )
    uniq = first & valid

    bounds, _, _ = concept_bounds(dtb, conc_s)
    bounds = jnp.where(valid, bounds, conc_s)  # freeze padding rows
    # a unique candidate c is dropped iff some candidate of the same instance
    # lies strictly inside (c, bound(c)) — i.e. a strict descendant is
    # present.  The sorted candidate array itself serves as the index: rows
    # in [R_right(inst, c), R_left(inst, bound)) are exactly those
    # descendants, so two binary searches decide the paper's interval test
    # exactly (duplicate runs included).
    L = pair64.searchsorted_pair(inst_s, conc_s, inst_s, conc_s, side="right")
    R = pair64.searchsorted_pair(inst_s, conc_s, inst_s, bounds, side="left")
    dropped_by_desc = R > L

    # spill intervals (multiple inheritance): candidate c is also dropped if
    # some candidate of the same instance lies in one of c's spill ranges.
    S = dtb.concept_spill_lo.shape[1]
    _, cpos, chit = concept_bounds(dtb, conc_s)
    any_spill_hit = jnp.zeros(conc_s.shape, bool)
    if S > 0:
        for lo_k, hi_k in zip(table_columns(dtb.concept_spill_lo, cpos),
                              table_columns(dtb.concept_spill_hi, cpos)):
            lo_k = jnp.where(chit, lo_k, 0)
            hi_k = jnp.where(chit, hi_k, 0)
            has = lo_k < hi_k
            L = pair64.searchsorted_pair(inst_s, conc_s, inst_s, lo_k, side="left")
            R = pair64.searchsorted_pair(inst_s, conc_s, inst_s, hi_k, side="left")
            any_spill_hit = any_spill_hit | (has & (R > L))

    keep = uniq & ~dropped_by_desc & ~any_spill_hit
    dropped_explicit = (uniq & expl_s & ~keep).astype(jnp.int32).sum()
    added_implicit = (keep & ~expl_s).astype(jnp.int32).sum()
    n_explicit_uniq = (uniq & expl_s).astype(jnp.int32).sum()
    return inst_s, conc_s, keep, n_explicit_uniq, dropped_explicit, added_implicit


@jax.jit
def _lite_materialize_device(spo, dtb: DeviceTBox):
    inst, conc, explicit = candidate_types(spo, dtb)
    inst_s, conc_s, keep, n_expl, n_drop, n_add = msc_select(inst, conc, explicit, dtb)

    # output: non-type triples unchanged + MSC type triples (both padded)
    is_type = spo[:, 1] == dtb.rdf_type_id
    nt = jnp.where(is_type[:, None], INVALID, spo)
    ty = jnp.stack(
        [
            jnp.where(keep, inst_s, INVALID),
            jnp.where(keep, jnp.int32(dtb.rdf_type_id), INVALID),
            jnp.where(keep, conc_s, INVALID),
        ],
        axis=1,
    )
    out = jnp.concatenate([nt, ty], axis=0)
    valid = out[:, 0] != INVALID
    stats = dict(
        n_explicit_unique=n_expl,
        n_deleted_explicit=n_drop,
        n_added_implicit=n_add,
        n_type_out=keep.astype(jnp.int32).sum(),
        n_nontype=(~is_type).astype(jnp.int32).sum(),
    )
    return out, valid, stats


def lite_materialize(kb, dtb: DeviceTBox | None = None):
    """kb.spo -> (materialized spo (padded), valid mask, stats dict)."""
    dtb = dtb or DeviceTBox.build(kb.tbox)
    out, valid, stats = _lite_materialize_device(kb.spo, dtb)
    return out, valid, {k: int(v) for k, v in stats.items()}


@partial(jax.jit, static_argnames="n")
def _take_valid(rows, valid, n: int):
    idx = jnp.nonzero(valid, size=n)[0]
    return jnp.stack([rows[:, c][idx] for c in range(rows.shape[1])], axis=1)


def compact_rows(rows, valid):
    """Drop padding rows, keeping order (host sync for the final count)."""
    return _take_valid(rows, valid, int(valid.sum()))
