"""Public jit'd wrappers for the Pallas kernels and the device-side ops.

Each kernel wrapper pads inputs to kernel block multiples, dispatches to the
Pallas implementation (interpret mode on CPU — the kernels TARGET TPU;
interpret executes the same kernel body for validation), slices padding
off, and matches the corresponding ``ref.py`` oracle exactly.  The
searches (``pair_search``, ``merge_gather``) are plain XLA on every
backend: they gather per lane, which Mosaic cannot lower.
"""
from __future__ import annotations

import threading
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.closure_expand import closure_expand_pallas
from repro.kernels.interval_filter import interval_filter_pallas
from repro.kernels.msc_select import msc_select_pallas
from repro.kernels.stream_compact import (
    MIN_BLOCK, dual_compact_pallas, interval_compact_pallas,
    masked_interval_compact_pallas, stream_compact_pallas,
)
from repro.utils import pair64

INVALID = np.int32(np.iinfo(np.int32).max)

# Trace-time kernel-pass accounting.  Each counter bumps while a wrapper's
# body is being TRACED (once per compiled executable, not per execution), so
# "how many kernel passes does this plan make over the store" is a
# deterministic, timing-free signal: reset, trace a cold plan, read.  The
# rewrite-mode dual-branch pin (one dual-mask pass instead of two
# single-mask passes) and the bench pass-count rows gate on these.
#
# Compiles can race under the threaded serving runtime (two workers tracing
# different plans concurrently), so every bump goes through _bump_pass: a
# lock guards the dict's read-modify-write, and each bump is mirrored into
# the process metrics registry (kernels/passes{kind=...}) where the obs
# exporters read it.  The dict itself stays the public read surface.
pass_counters = {"compact": 0, "dual_compact": 0, "merge": 0}
_PASS_LOCK = threading.Lock()


def _bump_pass(kind: str) -> None:
    from repro.obs.metrics import REGISTRY

    with _PASS_LOCK:
        pass_counters[kind] += 1
    REGISTRY.counter("kernels/passes", kind=kind).inc()


def reset_pass_counters() -> dict:
    """Zero the trace-time pass counters; returns the pre-reset snapshot."""
    with _PASS_LOCK:
        snap = dict(pass_counters)
        for k in pass_counters:
            pass_counters[k] = 0
    return snap


def _interpret() -> bool:
    """Interpret mode off the TPU: CPU runs execute the same kernel bodies
    through the Pallas interpreter; on a TPU every kernel compiles."""
    return jax.default_backend() != "tpu"


# Block-size selection for the compaction kernels.  Large stores take
# 4096-row tiles (fewer grid steps and stitch segments); small stores take
# the smallest TPU tile, one (8, 128) int32 vreg, so padding stays bounded.
LARGE_BLOCK = 4096
_LARGE_N = 1 << 16


def auto_block(n: int) -> int:
    """Compaction tile size for an n-row store (static at trace time)."""
    return LARGE_BLOCK if n >= _LARGE_N else MIN_BLOCK


def _pad1(x, m, fill):
    n = x.shape[0]
    p = (-n) % m
    if n == 0:
        p = m  # empty inputs still launch one (all-padding) tile: kernel
        # grids must be non-empty, and a delta-only store has a 0-row base
    if p == 0:
        return x
    return jnp.concatenate([x, jnp.full((p, *x.shape[1:]), fill, x.dtype)])


@partial(jax.jit, static_argnames=("block",))
def interval_filter(p, o, params, block: int = 4096):
    """LiteMat triple filter; params = int32[4] (plo, phi, olo, ohi) -> bool[N]."""
    n = p.shape[0]
    pp = _pad1(p, block, np.int32(np.iinfo(np.int32).max))
    po = _pad1(o, block, np.int32(np.iinfo(np.int32).max))
    out = interval_filter_pallas(pp, po, params, block=block, interpret=_interpret())
    return out[:n].astype(bool)


@partial(jax.jit, static_argnames=("group_block",))
def msc_select(conc, bounds, group_block: int = 128):
    """Grouped MSC keep-mask; conc/bounds int32[G, K] (-1 pad) -> bool[G, K]."""
    G = conc.shape[0]
    pc = _pad1(conc, group_block, np.int32(-1))
    pb = _pad1(bounds, group_block, np.int32(-1))
    out = msc_select_pallas(pc, pb, group_block=group_block, interpret=_interpret())
    return out[:G].astype(bool)


@partial(jax.jit, static_argnames=("block",))
def closure_expand(conc, sorted_ids, anc_table, block: int = 1024):
    """Ancestor-row expansion; conc int32[N] -> int32[N, D]."""
    n = conc.shape[0]
    pc = _pad1(conc, block, np.int32(-1))
    out = closure_expand_pallas(pc, sorted_ids, anc_table, block=block,
                                interpret=_interpret())
    return out[:n]


@jax.jit
def pair_search(table_hi, table_lo, qhi, qlo):
    """Lexicographic binary search (left) -> int32 positions.

    Plain XLA (``pair64.searchsorted_pair``): the table stays in HBM and
    every step is one vectorized gather, so there is no table-size ceiling.
    Mosaic has no per-lane gather from a VMEM ref, which a Pallas version
    of this search would need.
    """
    if table_hi.shape[0] == 0:  # empty table: every query lands at 0
        return jnp.zeros(qhi.shape, jnp.int32)
    return pair64.searchsorted_pair(table_hi, table_lo, qhi, qlo)


@jax.jit
def merge_gather(a_hi, a_lo, b_hi, b_lo):
    """Stable-merge gather map of two lex-sorted (hi, lo) pair runs.

    Returns int32[n + m]: values < n select run A, values >= n select
    ``B[value - n]`` — merged rows are one device gather away, so folding
    a sorted delta into a sorted base never assembles the merged array on
    the host.  Ties keep A-before-B order (the ``index.merge_sorted``
    contract; ``ref.ref_merge_sorted`` is the oracle).

    B row j lands at slot ``j + #{A <= B[j]}`` (one binary search per B
    row); every other slot takes the next A row in order, read off a
    prefix count of the B slots — O(m log n + n + m) in plain XLA.
    """
    n, m = a_hi.shape[0], b_hi.shape[0]
    if m == 0:
        return jnp.arange(n, dtype=jnp.int32)
    if n == 0:
        return jnp.arange(m, dtype=jnp.int32)
    _bump_pass("merge")
    slot_b = (pair64.searchsorted_pair(a_hi, a_lo, b_hi, b_lo, side="right")
              + jnp.arange(m, dtype=jnp.int32))
    is_b = jnp.zeros((n + m,), jnp.int32).at[slot_b].set(1)
    b_upto = jnp.cumsum(is_b)  # B rows in slots [0, i]
    i = jnp.arange(n + m, dtype=jnp.int32)
    return jnp.where(is_b == 1, n + b_upto - 1, i - b_upto)


def two_source_gather(base, delta, idx):
    """Gather rows addressed in combined [base | delta] coordinates.

    ``idx < base_n`` selects ``base[idx]``; the rest select
    ``delta[idx - base_n]`` — the virtual-concat addressing every live
    store view uses (core/delta.py keeps base and delta as SEPARATE device
    arrays so mutations never re-concatenate the base).  ``delta=None``
    (a delta-free view: combined coords never exceed the base) collapses
    to a plain base gather, so static stores pay no two-source overhead.
    """
    bn = base.shape[0]
    if delta is None or delta.shape[0] == 0:
        return base[jnp.clip(idx, 0, bn - 1)]
    if bn == 0:  # fully-compacted-away base: every coord is a delta coord
        return delta[jnp.clip(idx, 0, delta.shape[0] - 1)]
    b = base[jnp.clip(idx, 0, bn - 1)]
    from_d = idx >= bn
    d = delta[jnp.clip(idx - bn, 0, delta.shape[0] - 1)]
    if base.ndim > 1:
        from_d = from_d.reshape(from_d.shape + (1,) * (base.ndim - 1))
    return jnp.where(from_d, d, b)


def segment_positions(starts, lens, cap: int):
    """Map output slots [0, cap) onto k variable-length segments.

    One exclusive prefix sum over ``lens`` assigns every output slot j a
    (segment, rank-in-segment); returns (src = starts[seg] + rank,
    ok = j < total, total, seg).  Shared by the kernel-tile stitch below,
    the sorted-index range gather, and the index-nested-loop join (which
    needs ``seg`` to map each output row back to its probe row) in
    core/query.py — the searchsorted(side="right") addressing lives in
    exactly one place.
    """
    offsets = jnp.cumsum(lens)
    total = offsets[-1]
    begin = offsets - lens
    j = jnp.arange(cap, dtype=jnp.int32)
    seg = jnp.clip(jnp.searchsorted(offsets, j, side="right"),
                   0, lens.shape[0] - 1)
    src = starts[seg] + (j - begin[seg])
    return src, j < total, total, seg


def _assemble_compact(local, counts, cap: int, block: int):
    """Stitch tile-compacted indices into one front-compacted [cap] gather.

    The per-tile counts are the segment lengths (tile t's matches start at
    t*block); the total match count rides along for free — callers use it
    for overflow accounting instead of a second full counting pass.
    """
    tile_starts = jnp.arange(counts.shape[0], dtype=jnp.int32) * block
    src, ok, total, _ = segment_positions(tile_starts, counts, cap)
    take = jnp.where(ok, local[jnp.clip(src, 0, local.shape[0] - 1)], 0)
    return take, ok, total


@partial(jax.jit, static_argnames=("cap", "block"))
def compact_indices(mask, cap: int, block: int = MIN_BLOCK):
    """Stable compaction of an arbitrary bool mask.

    Returns (take int32[cap] — indices of the first cap True positions,
    0-filled past the end; ok bool[cap]; total int32 match count).  Replaces
    the ``jnp.argsort(~mask, stable=True)[:cap]`` idiom in O(N).
    """
    _bump_pass("compact")
    m = _pad1(mask.astype(jnp.int32), block, np.int32(0))
    local, counts = stream_compact_pallas(m, block=block, interpret=_interpret())
    return _assemble_compact(local, counts, cap, block)


@partial(jax.jit, static_argnames=("cap", "block"))
def dual_compact_indices(mask_a, mask_b, cap: int,
                         block: int = MIN_BLOCK):
    """Stable compaction of TWO bool masks over the same rows in ONE pass.

    Returns (take_a, ok_a, total_a, take_b, ok_b, total_b) — each triple
    exactly what ``compact_indices`` returns for its mask, but the store is
    streamed through the kernel once (the rewrite-mode dual-branch type
    pattern compacts a subject-binding and an object-binding mask over the
    same rows; this halves its kernel passes).
    """
    _bump_pass("dual_compact")
    ma = _pad1(mask_a.astype(jnp.int32), block, np.int32(0))
    mb = _pad1(mask_b.astype(jnp.int32), block, np.int32(0))
    la, ca, lb, cb = dual_compact_pallas(ma, mb, block=block,
                                         interpret=_interpret())
    return (*_assemble_compact(la, ca, cap, block),
            *_assemble_compact(lb, cb, cap, block))


@partial(jax.jit, static_argnames=("cap", "block"))
def interval_compact(p, o, params, cap: int, block: int = MIN_BLOCK):
    """Fused LiteMat interval predicate + compaction in one pass.

    params = int32[4] (plo, phi, olo, ohi); padding uses INT32_MAX which can
    never satisfy ``p < phi`` for any real predicate bound.  Same returns as
    ``compact_indices``.
    """
    _bump_pass("compact")
    pp = _pad1(p, block, INVALID)
    po = _pad1(o, block, INVALID)
    local, counts = interval_compact_pallas(pp, po, params, block=block,
                                            interpret=_interpret())
    return _assemble_compact(local, counts, cap, block)


@partial(jax.jit, static_argnames=("cap", "block"))
def masked_interval_compact(p, o, alive, params, cap: int,
                            block: int = MIN_BLOCK):
    """Fused interval predicate + liveness mask + compaction in one pass.

    The live-store scan primitive: ``alive`` carries tombstones from the
    delta overlay (core/delta.py), so a deleted row is filtered in the same
    kernel pass that evaluates the LiteMat interval predicate.  Same
    returns as ``compact_indices``.
    """
    _bump_pass("compact")
    pp = _pad1(p, block, INVALID)
    po = _pad1(o, block, INVALID)
    pa = _pad1(alive.astype(jnp.int32), block, np.int32(0))
    local, counts = masked_interval_compact_pallas(
        pp, po, pa, params, block=block, interpret=_interpret())
    return _assemble_compact(local, counts, cap, block)


__all__ = [
    "interval_filter", "msc_select", "closure_expand", "pair_search",
    "compact_indices", "dual_compact_indices",
    "interval_compact", "masked_interval_compact", "merge_gather",
    "two_source_gather", "segment_positions", "auto_block", "LARGE_BLOCK",
    "pass_counters", "reset_pass_counters", "ref",
]
