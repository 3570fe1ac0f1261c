"""Full RDFS materialization — the paper's baseline (Table V).

Forward-chains the RDFS rules the paper targets (rdfs2/3 domain/range,
rdfs5/7 sub-property, rdfs9/11 sub-class) in one pass: thanks to the prefix
encoding, the sub-class/sub-property closure of an id is just its DAG
ancestor row (precomputed table; pure gathers on device — no joins), and the
one candidate pass of materialize.py already folds domain/range through
effective property-ancestor tables.  Synthetic roots (our __root__ nodes,
id 0) are not materialized, matching the paper's datasets which never store
owl:Thing types.

Output is a padded, lexicographically sorted, deduplicated triple array —
the "much longer + bigger store" whose cost Table V measures.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.materialize import (
    INVALID, DeviceTBox, candidate_types, sort_rows, table_columns,
)


def _unique_sorted(s, p, o):
    """Head-of-run mask over sorted rows, padding (INVALID) excluded."""
    first = jnp.concatenate(
        [
            jnp.ones((1,), bool),
            (s[1:] != s[:-1]) | (p[1:] != p[:-1]) | (o[1:] != o[:-1]),
        ]
    )
    return first & (s != INVALID)


def _dedup_rows(s, p, o):
    """Sort rows lexicographically; return sorted cols + unique&valid mask."""
    s, p, o = sort_rows(s, p, o)
    return s, p, o, _unique_sorted(s, p, o)


def _closure_columns(spo, dtb: DeviceTBox):
    """Every candidate row of the closure as three 1-D columns (INVALID
    where a slot holds no row); duplicates included."""
    s, p, o = spo[:, 0], spo[:, 1], spo[:, 2]
    is_type = p == dtb.rdf_type_id
    type_id = jnp.int32(dtb.rdf_type_id)
    cols_s, cols_p, cols_o = [s], [p], [o]

    # 1. property closure on non-type triples: (s, anc(p), o) --------------
    ppos = jnp.searchsorted(dtb.prop_sorted_ids, p)
    ppos = jnp.clip(ppos, 0, dtb.prop_sorted_ids.shape[0] - 1)
    p_known = (dtb.prop_sorted_ids[ppos] == p) & ~is_type
    for anc in table_columns(dtb.prop_ancestors, ppos):
        ok = p_known & (anc > 0)  # exclude synthetic root (id 0)
        cols_s.append(jnp.where(ok, s, INVALID))
        cols_p.append(jnp.where(ok, anc, INVALID))
        cols_o.append(jnp.where(ok, o, INVALID))

    # 2. type candidates (explicit + effective domain/range) ---------------
    inst, conc, _ = candidate_types(spo, dtb)
    cvalid = inst != INVALID
    cols_s.append(inst)
    cols_p.append(jnp.where(cvalid, type_id, INVALID))
    cols_o.append(conc)

    # 3. concept closure on every candidate: (inst, type, anc(conc)) -------
    cpos = jnp.searchsorted(dtb.concept_sorted_ids, conc)
    cpos = jnp.clip(cpos, 0, dtb.concept_sorted_ids.shape[0] - 1)
    c_known = cvalid & (dtb.concept_sorted_ids[cpos] == conc)
    for anc in table_columns(dtb.concept_ancestors, cpos):
        ok = c_known & (anc > 0)
        cols_s.append(jnp.where(ok, inst, INVALID))
        cols_p.append(jnp.where(ok, type_id, INVALID))
        cols_o.append(jnp.where(ok, anc, INVALID))

    all_s = jnp.concatenate(cols_s)
    invalid = all_s == INVALID
    return (all_s, jnp.where(invalid, INVALID, jnp.concatenate(cols_p)),
            jnp.where(invalid, INVALID, jnp.concatenate(cols_o)))


@jax.jit
def _full_materialize_device(spo, dtb: DeviceTBox):
    s_s, p_s, o_s, uniq = _dedup_rows(*_closure_columns(spo, dtb))
    # original-dataset unique count (denominator of the paper's "+%")
    ouniq = _dedup_rows(spo[:, 0], spo[:, 1], spo[:, 2])[3]
    stats = dict(
        n_closure=uniq.astype(jnp.int32).sum(),
        n_original_unique=ouniq.astype(jnp.int32).sum(),
    )
    return jnp.stack([s_s, p_s, o_s], axis=1), uniq, stats


# Input rows per closure pass of ``full_materialize``.  A row expands into
# up to 1 + DP + (1 + Kd + Kr)(1 + D) candidate rows (about 30 on LUBM);
# one pass over a whole LUBM-100 store would need several times the
# chip's HBM for them.
CHUNK_ROWS = 1 << 20


@jax.jit
def _closure_chunk(spo, dtb: DeviceTBox):
    """One chunk's distinct closure rows, sorted, in front; -> (s, p, o,
    count).  Duplicates become padding and a second sort sinks them."""
    s, p, o, uniq = _dedup_rows(*_closure_columns(spo, dtb))
    s, p, o = sort_rows(*(jnp.where(uniq, c, INVALID) for c in (s, p, o)))
    return s, p, o, uniq.astype(jnp.int32).sum()


@jax.jit
def _union_closure(s, p, o, spo):
    s, p, o, uniq = _dedup_rows(s, p, o)
    ouniq = _dedup_rows(spo[:, 0], spo[:, 1], spo[:, 2])[3]
    stats = dict(
        n_closure=uniq.astype(jnp.int32).sum(),
        n_original_unique=ouniq.astype(jnp.int32).sum(),
    )
    return jnp.stack([s, p, o], axis=1), uniq, stats


def full_materialize(kb, dtb: DeviceTBox | None = None):
    """kb.spo -> (closed spo (sorted, padded), valid mask, stats).

    The closure of every ``CHUNK_ROWS`` input rows is deduplicated on its
    own; the chunks' distinct rows are then merged and deduplicated once
    more.  Stores of at most one chunk take a single pass.
    """
    dtb = dtb or DeviceTBox.build(kb.tbox)
    spo = kb.spo
    n = spo.shape[0]
    if n <= CHUNK_ROWS:
        out, valid, stats = _full_materialize_device(spo, dtb)
    else:
        parts = []
        for a in range(0, n, CHUNK_ROWS):
            chunk = spo[a:a + CHUNK_ROWS]
            if chunk.shape[0] < CHUNK_ROWS:  # one executable for every chunk
                chunk = jnp.concatenate([chunk, jnp.full(
                    (CHUNK_ROWS - chunk.shape[0], 3), INVALID, jnp.int32)])
            s, p, o, k = _closure_chunk(chunk, dtb)
            k = int(k)
            parts.append((s[:k], p[:k], o[:k]))
        cols = [jnp.concatenate(c) for c in zip(*parts)]
        out, valid, stats = _union_closure(*cols, spo)
    st = {k: int(v) for k, v in stats.items()}
    st["added_pct"] = 100.0 * (st["n_closure"] - st["n_original_unique"]) / max(
        st["n_original_unique"], 1
    )
    return out, valid, st
