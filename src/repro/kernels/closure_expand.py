"""Pallas TPU kernel: RDFS closure expansion via the prefix encoding.

The hot loop of the full-materialization baseline (paper Table V): for every
type assertion, emit the concept's ancestor id row.  Thanks to LiteMat's
encoding, ancestors are a precomputed (C, D) table indexed by a binary
search over the sorted concept ids — both of which fit comfortably in VMEM
(Wikidata-scale: 213K x 4B ids = 0.9 MB; ancestor table a few MB).

The kernel fuses search + row gather per ``block`` of query ids: the concept
table is resident (constant index map), queries stream through.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 1024


def _kernel(ids_ref, anc_ref, q_ref, out_ref):
    q = q_ref[...]  # (B,)
    C = ids_ref.shape[0]
    steps = max(1, int(np.ceil(np.log2(max(C, 2)))) + 1)

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) >> 1
        mv = ids_ref[mid]  # vector gather from the VMEM-resident table
        go = mv < q
        lo = jnp.where(go & (lo < hi), mid + 1, lo)
        hi = jnp.where((~go) & (lo < hi), mid, hi)
        return lo, hi

    lo0 = jnp.zeros(q.shape, jnp.int32)
    hi0 = jnp.full(q.shape, C, jnp.int32)
    pos, _ = lax.fori_loop(0, steps, body, (lo0, hi0))
    pos = jnp.clip(pos, 0, C - 1)
    hit = ids_ref[pos] == q
    rows = anc_ref[pos]  # (B, D) row gather
    out_ref[...] = jnp.where(hit[:, None], rows, -1)


def closure_expand_pallas(conc, sorted_ids, anc_table, *, block: int = DEFAULT_BLOCK,
                          interpret: bool = False):
    """conc: int32[N]; sorted_ids: int32[C]; anc_table: int32[C, D] -> [N, D]."""
    n = conc.shape[0]
    C, D = anc_table.shape
    grid = (pl.cdiv(n, block),)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C,), lambda i: (0,)),  # resident table
            pl.BlockSpec((C, D), lambda i: (0, 0)),  # resident ancestors
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, D), jnp.int32),
        interpret=interpret,
        name="closure_expand",
    )(sorted_ids, anc_table, conc)
