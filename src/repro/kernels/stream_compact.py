"""Pallas TPU kernel: stable stream compaction (count -> prefix-sum -> shift).

The query engine's hot idiom was ``jnp.argsort(~mask, stable=True)[:cap]`` —
an O(N log N) sort just to move matching rows to the front.  Compaction is
the right primitive: each ``block``-sized tile moves the *global row
indices* of its matches to the front of its output tile (INVALID padding
behind) and reports its match count.  The host wrapper (kernels/ops.py)
stitches tiles together with one exclusive prefix sum over the per-tile
counts plus a single gather — O(N) total, and the per-tile counts double as
the match count, so the engine needs no separate counting pass.

A tile is a ``(block // 128, 128)`` int32 slab: rows on sublanes, 128
lanes, so ``block`` is a multiple of one (8, 128) vreg tile (1024 rows).
The body uses only element-wise ops, two small matmuls and ``pltpu.roll``
— Mosaic has no vector gather, scatter, cumsum or unaligned dynamic
store, and needs none of them here:

  1. a prefix count of the non-matches gives every match its
     displacement ``d``: an upper-triangular 0/1 matmul scans the lanes of
     each row, a strictly-lower-triangular one adds the rows before it
     (f32 on 0/1 and <= 128 inputs with f32 accumulation: exact);
  2. a log-step shift network moves each match left by ``d``: step ``s``
     (1, 2, 4, ...) shifts the matches whose ``d`` has bit ``s`` set by
     ``s`` slots.  Taking the bits low to high never lands two matches on
     one slot (their gap always exceeds the difference of their partial
     shifts), so each step is one roll plus two selects.  The steps run
     as a ``fori_loop`` with the shift as a traced value, which keeps the
     program small for the interpreter and the CPU compiler.

Matches travel packed as ``d << 16 | local index`` (-1 marks an empty
slot), which caps ``block`` at 2**15.

Four entry points share the body:

  * ``stream_compact_pallas``   — compacts an arbitrary precomputed mask
    (spill intervals, member sets, rewrite-mode type masks),
  * ``interval_compact_pallas`` — fuses the LiteMat interval predicate
    ``p in [plo, phi) AND o in [olo, ohi)`` (constants in SMEM) with
    compaction in ONE pass over the store,
  * ``masked_interval_compact_pallas`` — the live-store variant: the same
    predicate ANDed with a per-row liveness (tombstone) mask,
  * ``dual_compact_pallas``     — TWO masks over the same rows compacted
    into two independent output streams in one grid pass (the rewrite-mode
    type pattern's subject- and object-binding branches).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MIN_BLOCK = 8 * LANES  # one (8, 128) int32 vreg tile
MAX_BLOCK = 1 << 15  # local index and displacement share one int32
DEFAULT_BLOCK = 4096
INVALID = np.int32(np.iinfo(np.int32).max)


def _check_block(n: int, block: int) -> None:
    if block % MIN_BLOCK or not MIN_BLOCK <= block <= MAX_BLOCK:
        raise ValueError(f"block {block} must be a multiple of {MIN_BLOCK} "
                         f"in [{MIN_BLOCK}, {MAX_BLOCK}]")
    if n % block:
        raise ValueError(f"length {n} is not a multiple of block {block}")


def _roll_flat(x, k):
    """``jnp.roll`` by traced ``k`` in [0, size) over the row-major
    flattening of a (rows, 128) tile: ``y[p] = x[(p - k) mod size]``, built
    from one lane roll and two sublane rolls."""
    rows = x.shape[0]
    q, r = k // LANES, k % LANES
    a = pltpu.roll(x, r, 1)
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane >= r, pltpu.roll(a, q % rows, 0),
                     pltpu.roll(a, (q + 1) % rows, 0))


def _iotas(n: int):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0),
            lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _prefix_count(z):
    """Inclusive prefix sum of a 0/1 (rows, 128) tile over its row-major
    flattening, as two 0/1 matmuls."""
    zf = z.astype(jnp.float32)
    i, j = _iotas(LANES)
    lanes = jnp.dot(zf, (i <= j).astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    row_tot = jnp.broadcast_to(jnp.sum(zf, axis=1, keepdims=True), zf.shape)
    i, j = _iotas(z.shape[0])
    rows_before = jnp.dot((j < i).astype(jnp.float32), row_tot,
                          preferred_element_type=jnp.float32)
    return (lanes + rows_before).astype(jnp.int32)


def _compact_tile(m, base):
    """m: int32 (rows, 128) 0/1 tile -> (front-compacted global indices,
    INVALID-padded; tile match count)."""
    size = m.shape[0] * LANES
    flat = (lax.broadcasted_iota(jnp.int32, m.shape, 0) * LANES
            + lax.broadcasted_iota(jnp.int32, m.shape, 1))
    z = 1 - m
    d = _prefix_count(z) - z  # non-matches strictly before each slot
    x = jnp.where(m != 0, (d << 16) | flat, -1)

    def step(k, x):
        s = jnp.left_shift(jnp.int32(1), k)
        # y[p] = x[p + s]; slots past the tile end wrap to its head, where
        # no match can still owe a shift of s, so they never arrive
        up = _roll_flat(x, size - s)
        arrive = (up >= 0) & (((up >> 16) & s) != 0)
        leave = (x >= 0) & (((x >> 16) & s) != 0)
        return jnp.where(arrive, up, jnp.where(leave, -1, x))

    x = lax.fori_loop(0, size.bit_length() - 1, step, x)
    return jnp.where(x >= 0, (x & 0xFFFF) + base, INVALID), jnp.sum(m)


def _emit(m, idx_ref, cnt_ref):
    base = pl.program_id(0) * (idx_ref.shape[0] * LANES)
    vals, cnt = _compact_tile(m, base)
    idx_ref[...] = vals
    cnt_ref[...] = jnp.full(cnt_ref.shape, cnt, jnp.int32)


def _mask_kernel(mask_ref, idx_ref, cnt_ref):
    _emit(mask_ref[...], idx_ref, cnt_ref)


def _interval_mask(params_ref, p_ref, o_ref):
    plo, phi = params_ref[0], params_ref[1]
    olo, ohi = params_ref[2], params_ref[3]
    p = p_ref[...]
    o = o_ref[...]
    return (p >= plo) & (p < phi) & (o >= olo) & (o < ohi)


def _fused_kernel(params_ref, p_ref, o_ref, idx_ref, cnt_ref):
    m = _interval_mask(params_ref, p_ref, o_ref)
    _emit(m.astype(jnp.int32), idx_ref, cnt_ref)


def _masked_fused_kernel(params_ref, p_ref, o_ref, alive_ref, idx_ref,
                         cnt_ref):
    m = _interval_mask(params_ref, p_ref, o_ref) & (alive_ref[...] != 0)
    _emit(m.astype(jnp.int32), idx_ref, cnt_ref)


def _dual_kernel(ma_ref, mb_ref, idxa_ref, cnta_ref, idxb_ref, cntb_ref):
    _emit(ma_ref[...], idxa_ref, cnta_ref)
    _emit(mb_ref[...], idxb_ref, cntb_ref)


def _compact_call(kernel, name: str, n: int, block: int, n_smem: int,
                  n_tiled: int, streams: int, interpret: bool):
    """pallas_call ``name`` over ``n // block`` tiles of (block // 128, 128)
    rows.

    Each stream emits its compacted indices tile-for-tile and its count
    broadcast over one (1, 1, 128) block — a rank-1 (1,) block per tile is
    not a TPU tiling.
    """
    _check_block(n, block)
    nb, rows = n // block, block // LANES
    tile = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    count = pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=([pl.BlockSpec(memory_space=pltpu.SMEM)] * n_smem
                  + [tile] * n_tiled),
        out_specs=[tile, count] * streams,
        out_shape=[jax.ShapeDtypeStruct((n // LANES, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1, LANES), jnp.int32)] * streams,
        interpret=interpret,
        name=name,
    )


def _tiles(x):
    return x.reshape(-1, LANES)


def _streams(outs):
    """Kernel outputs -> flat (indices int32[N], counts int32[N/block]) per
    stream, in order."""
    res = []
    for i in range(0, len(outs), 2):
        res += [outs[i].reshape(-1), outs[i + 1][:, 0, 0]]
    return tuple(res)


def stream_compact_pallas(mask, *, block: int = DEFAULT_BLOCK,
                          interpret: bool = False):
    """mask: int32[N] 0/1 (N a multiple of block) ->
    (tile-compacted global indices int32[N], per-tile counts int32[N/block])."""
    n = mask.shape[0]
    call = _compact_call(_mask_kernel, "stream_compact", n, block, 0, 1, 1,
                         interpret)
    return _streams(call(_tiles(mask)))


def interval_compact_pallas(p, o, params, *, block: int = DEFAULT_BLOCK,
                            interpret: bool = False):
    """p, o: int32[N]; params: int32[4] = (plo, phi, olo, ohi) ->
    (tile-compacted match indices, per-tile counts) — predicate fused."""
    n = p.shape[0]
    call = _compact_call(_fused_kernel, "interval_compact", n, block, 1, 2,
                         1, interpret)
    return _streams(call(params, _tiles(p), _tiles(o)))


def masked_interval_compact_pallas(p, o, alive, params, *,
                                   block: int = DEFAULT_BLOCK,
                                   interpret: bool = False):
    """p, o, alive: int32[N]; params: int32[4] = (plo, phi, olo, ohi) ->
    (tile-compacted match indices, per-tile counts) — interval predicate and
    tombstone filter fused in one pass."""
    n = p.shape[0]
    call = _compact_call(_masked_fused_kernel, "masked_interval_compact", n,
                         block, 1, 3, 1, interpret)
    return _streams(call(params, _tiles(p), _tiles(o), _tiles(alive)))


def dual_compact_pallas(mask_a, mask_b, *, block: int = DEFAULT_BLOCK,
                        interpret: bool = False):
    """Two int32[N] masks -> two (indices, per-tile counts) streams, one pass.

    Each stream independently satisfies the ``stream_compact_pallas``
    contract; the tile's rows are resident once while BOTH masks resolve,
    so the dual-branch consumer pays one grid pass instead of two.
    """
    n = mask_a.shape[0]
    call = _compact_call(_dual_kernel, "dual_compact", n, block, 0, 2, 2,
                         interpret)
    return _streams(call(_tiles(mask_a), _tiles(mask_b)))
