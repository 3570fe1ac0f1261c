"""Pallas TPU kernel: grouped Most-Specific-Concept selection (paper §IV).

Input layout is the TPU-native form of the MSC pass: the data pipeline
groups each instance's candidate concepts into padded rows (G groups x K
candidate slots, -1 padding).  A candidate is kept iff no other candidate of
the same group is a strict descendant (id strictly inside its subsumption
interval) and it is not a duplicate of an earlier slot.

K is small (an instance rarely has more than a few dozen candidate types —
DBPedia averages 8), so the O(K^2) broadcast compare is ideal VPU work: a
(Bg, K, K) bool cube per tile, no gathers, no sorts.  This replaces the
sort-based one-pass scan the distributed path uses — same contract
(ref_msc_select), different memory-access pattern, chosen because on TPU the
pairwise form keeps everything in registers/VMEM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_GROUP_BLOCK = 128


def _kernel(conc_ref, bnd_ref, keep_ref):
    c = conc_ref[...]  # (Bg, K) int32
    b = bnd_ref[...]
    valid = c >= 0
    c1 = c[:, :, None]  # candidate under test
    b1 = b[:, :, None]
    c2 = c[:, None, :]  # the other candidates
    v2 = valid[:, None, :]
    strict_desc = v2 & (c2 > c1) & (c2 < b1)
    K = c.shape[1]
    j_idx = lax.broadcasted_iota(jnp.int32, (1, K, K), 1)
    k_idx = lax.broadcasted_iota(jnp.int32, (1, K, K), 2)
    dup = v2 & (c2 == c1) & (j_idx > k_idx)  # earlier slot wins
    drop = (strict_desc | dup).any(axis=2)
    keep_ref[...] = (valid & ~drop).astype(jnp.int32)


def msc_select_pallas(conc, bounds, *, group_block: int = DEFAULT_GROUP_BLOCK,
                      interpret: bool = False):
    """conc/bounds: int32[G, K] (-1 padded) -> int32 keep mask [G, K]."""
    G, K = conc.shape
    grid = (pl.cdiv(G, group_block),)
    spec = pl.BlockSpec((group_block, K), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((G, K), jnp.int32),
        interpret=interpret,
        name="msc_select",
    )(conc, bounds)
