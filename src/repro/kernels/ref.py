"""Pure-jnp oracles for every kernel and device op (the correctness contracts).

Each ``ref_*`` function defines the exact semantics its kernel must match;
tests sweep shapes/dtypes and ``assert_allclose`` kernel-vs-ref (interpret
mode on CPU, compiled on real TPUs).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

INVALID = jnp.int32(np.iinfo(np.int32).max)


def ref_interval_filter(s, p, o, plo, phi, olo, ohi, type_id):
    """LiteMat triple-pattern mask: p in [plo, phi) AND (o-interval applies
    only when the pattern is an rdf:type pattern, signalled by plo==type_id
    and phi==type_id+1; otherwise o in [olo, ohi) with olo=INT_MIN meaning
    'unconstrained')."""
    m = (p >= plo) & (p < phi)
    m = m & ((o >= olo) & (o < ohi))
    return m


def ref_msc_select(conc, bounds):
    """Grouped MSC: conc/bounds are (G, K) candidate concept ids (-1 pad).

    keep[g, j] = candidate j is valid and no other candidate of group g lies
    strictly inside (conc[g, j], bounds[g, j]) and no duplicate with a lower
    index exists (first occurrence wins).
    """
    valid = conc >= 0
    c1 = conc[:, :, None]  # candidate under test (j)
    b1 = bounds[:, :, None]
    c2 = conc[:, None, :]  # the other candidates (k)
    v2 = valid[:, None, :]
    strict_desc = v2 & (c2 > c1) & (c2 < b1)
    K = conc.shape[1]
    earlier = jnp.arange(K)[None, :, None] > jnp.arange(K)[None, None, :]
    dup = v2 & (c2 == c1) & earlier
    drop = (strict_desc | dup).any(axis=2)
    return valid & ~drop


def ref_closure_expand(conc, sorted_ids, anc_table):
    """For each concept id, its DAG-ancestor id row (-1 where absent/pad)."""
    pos = jnp.clip(jnp.searchsorted(sorted_ids, conc), 0, sorted_ids.shape[0] - 1)
    hit = sorted_ids[pos] == conc
    return jnp.where(hit[:, None], anc_table[pos], -1)


def ref_stream_compact(mask, block: int):
    """Tile-local stable compaction: (global match indices, per-tile counts).

    mask length must be a multiple of ``block``.  Tile t's output slice
    ``[t*block:(t+1)*block]`` holds the global indices of its set mask bits
    in ascending order, INVALID-padded — the contract of
    ``stream_compact_pallas`` / ``interval_compact_pallas``.
    """
    n = mask.shape[0]
    nb = n // block
    m = jnp.asarray(mask).astype(jnp.int32).reshape(nb, block)
    cnt = m.sum(axis=1)
    order = jnp.argsort(1 - m, axis=1, stable=True)  # matches first, in order
    gidx = jnp.arange(nb, dtype=jnp.int32)[:, None] * block + order.astype(jnp.int32)
    slot = jnp.arange(block, dtype=jnp.int32)[None, :]
    local = jnp.where(slot < cnt[:, None], gidx, INVALID)
    return local.reshape(-1), cnt.astype(jnp.int32)


def ref_dual_compact(mask_a, mask_b, block: int):
    """Two independent tile-local compactions of masks over the same rows.

    The dual-mask kernel streams the tile once and emits both streams; its
    contract is simply ``ref_stream_compact`` applied to each mask — order
    of streams preserved, no interaction between them.
    """
    la, ca = ref_stream_compact(mask_a, block)
    lb, cb = ref_stream_compact(mask_b, block)
    return la, ca, lb, cb


def ref_merge_sorted(a_hi, a_lo, b_hi, b_lo):
    """Gather map of the stable merge of two lex-sorted (hi, lo) runs.

    out[i] < n means merged slot i holds A[out[i]]; out[i] >= n means it
    holds B[out[i] - n].  Ties place A rows before B rows (the host
    ``index.merge_sorted`` contract: searchsorted side='right' for B) —
    the semantics ``ops.merge_gather`` must match exactly.
    """
    from repro.utils import pair64

    n, m = a_hi.shape[0], b_hi.shape[0]
    if m == 0:
        return jnp.arange(n, dtype=jnp.int32)
    if n == 0:
        return jnp.arange(m, dtype=jnp.int32)
    pos_a = pair64.searchsorted_pair(b_hi, b_lo, a_hi, a_lo, side="left")
    pos_b = pair64.searchsorted_pair(a_hi, a_lo, b_hi, b_lo, side="right")
    out = jnp.zeros(n + m, dtype=jnp.int32)
    out = out.at[pos_a + jnp.arange(n, dtype=jnp.int32)].set(
        jnp.arange(n, dtype=jnp.int32))
    out = out.at[pos_b + jnp.arange(m, dtype=jnp.int32)].set(
        n + jnp.arange(m, dtype=jnp.int32))
    return out
