"""Pallas TPU kernel: LiteMat interval triple filter.

The hottest loop of the paper's query processor (§V): for every stored
triple, decide ``plo <= p < phi AND olo <= o < ohi`` — one fused compare
replacing the UNION over a whole sub-hierarchy.  Pure streaming VPU work:
triples flow HBM -> VMEM in ``block``-sized column tiles; the four interval
constants sit in SMEM (they are per-query runtime values, not compile-time
constants, so serving does not re-specialize).

Block shape: 1-D tiles of ``block`` elements per column (multiple of 1024 =
8 sublanes x 128 lanes on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 4096


def _kernel(params_ref, p_ref, o_ref, out_ref):
    plo = params_ref[0]
    phi = params_ref[1]
    olo = params_ref[2]
    ohi = params_ref[3]
    p = p_ref[...]
    o = o_ref[...]
    m = (p >= plo) & (p < phi) & (o >= olo) & (o < ohi)
    out_ref[...] = m.astype(jnp.int32)


def interval_filter_pallas(p, o, params, *, block: int = DEFAULT_BLOCK, interpret: bool = False):
    """p, o: int32[N]; params: int32[4] = (plo, phi, olo, ohi) -> int32 mask."""
    n = p.shape[0]
    grid = (pl.cdiv(n, block),)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
        name="interval_filter",
    )(params, p, o)
