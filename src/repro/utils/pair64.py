"""62-bit keys as (hi, lo) int32 pairs — the TPU-native fingerprint form.

TPUs have no fast int64 (and JAX x64 is off by default), so every device-side
dictionary operation works on two parallel int32 planes holding the top/bottom
31 bits of a 62-bit fingerprint.  Lexicographic (hi, lo) order equals numeric
order of the original value, so sort / unique / binary-search all transfer.

The vectorized binary search below is the device search every backend runs
(``kernels/ops.pair_search`` wraps it).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

WORD_BITS = 31
WORD_MASK = (1 << WORD_BITS) - 1


# -- host conversions --------------------------------------------------------

def split_np(fp: np.ndarray):
    """int64 62-bit values -> (hi, lo) int32 numpy planes."""
    fp = np.asarray(fp, dtype=np.int64)
    return (fp >> WORD_BITS).astype(np.int32), (fp & WORD_MASK).astype(np.int32)


def combine_np(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (np.asarray(hi, dtype=np.int64) << WORD_BITS) | np.asarray(lo, dtype=np.int64)


# -- device ops ---------------------------------------------------------------

def pair_less(ahi, alo, bhi, blo):
    return (ahi < bhi) | ((ahi == bhi) & (alo < blo))


def pair_eq(ahi, alo, bhi, blo):
    return (ahi == bhi) & (alo == blo)


def sort_pairs(hi, lo):
    """Sort pairs lexicographically; returns (hi_s, lo_s, perm)."""
    perm = jnp.lexsort((lo, hi))
    return hi[perm], lo[perm], perm


def unique_mask_sorted(hi_s, lo_s):
    """mask[i] = True iff pair i differs from pair i-1 (first occurrence)."""
    prev_ne = ~pair_eq(hi_s[1:], lo_s[1:], hi_s[:-1], lo_s[:-1])
    return jnp.concatenate([jnp.ones((1,), dtype=bool), prev_ne])


def searchsorted_pair(table_hi, table_lo, qhi, qlo, side: str = "left"):
    """Vectorized binary search over a lex-sorted pair table.

    Returns, per query, the insertion index (side='left') — ~34 gather steps
    regardless of query count.
    """
    import jax.lax as lax

    n = table_hi.shape[0]
    steps = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    left = side == "left"

    def step(_, carry):
        lo_b, hi_b = carry
        mid = (lo_b + hi_b) >> 1
        mh = table_hi[mid]
        ml = table_lo[mid]
        go_right = pair_less(mh, ml, qhi, qlo) if left else ~pair_less(qhi, qlo, mh, ml)
        lo_n = jnp.where(go_right & (lo_b < hi_b), mid + 1, lo_b)
        hi_n = jnp.where((~go_right) & (lo_b < hi_b), mid, hi_b)
        return lo_n, hi_n

    lo_b = jnp.zeros(qhi.shape, dtype=jnp.int32)
    hi_b = jnp.full(qhi.shape, n, dtype=jnp.int32)
    lo_b, _ = lax.fori_loop(0, steps, step, (lo_b, hi_b))
    return lo_b


def lookup_pair(table_hi, table_lo, values, qhi, qlo, default=-1):
    """Exact-match lookup: value for each query pair, ``default`` if absent."""
    pos = searchsorted_pair(table_hi, table_lo, qhi, qlo)
    pos_c = jnp.clip(pos, 0, table_hi.shape[0] - 1)
    hit = pair_eq(table_hi[pos_c], table_lo[pos_c], qhi, qlo)
    return jnp.where(hit, values[pos_c], default), hit
