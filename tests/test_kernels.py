"""Pallas kernels and device ops vs pure-jnp oracles: shape/dtype sweeps +
property tests.

Kernels run in interpret mode on CPU (the kernel bodies execute verbatim);
on a real TPU the same wrappers compile the Mosaic path
(tests/test_tpu_compile.py compiles them for a v5e without one).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels import ops, ref


@pytest.mark.parametrize("n", [1, 100, 4096, 5000])
@pytest.mark.parametrize("block", [1024, 4096])
def test_interval_filter_sweep(n, block, rng):
    p = jnp.asarray(rng.integers(0, 1000, n), jnp.int32)
    o = jnp.asarray(rng.integers(0, 1 << 20, n), jnp.int32)
    params = jnp.asarray([100, 300, 0, 1 << 19], jnp.int32)
    got = ops.interval_filter(p, o, params, block=block)
    want = ref.ref_interval_filter(None, p, o, 100, 300, 0, 1 << 19, 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("G,K", [(1, 4), (37, 16), (130, 8), (64, 33)])
def test_msc_select_sweep(G, K, rng):
    conc = rng.integers(-1, 500, (G, K)).astype(np.int32)
    bounds = conc + rng.integers(1, 64, (G, K)).astype(np.int32)
    got = ops.msc_select(jnp.asarray(conc), jnp.asarray(bounds))
    want = ref.ref_msc_select(jnp.asarray(conc), jnp.asarray(bounds))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(1, 12), st.integers(2, 24), st.integers(0, 2**31 - 2))
@settings(max_examples=25, deadline=None)
def test_msc_select_property(g, k, seed):
    rng = np.random.default_rng(seed)
    conc = rng.integers(-1, 100, (g, k)).astype(np.int32)
    bounds = conc + rng.integers(1, 32, (g, k)).astype(np.int32)
    got = np.asarray(ops.msc_select(jnp.asarray(conc), jnp.asarray(bounds)))
    want = np.asarray(ref.ref_msc_select(jnp.asarray(conc), jnp.asarray(bounds)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("C,D,n", [(5, 3, 10), (64, 8, 2048), (513, 5, 100)])
def test_closure_expand_sweep(C, D, n, rng):
    sorted_ids = jnp.asarray(
        np.sort(rng.choice(1 << 20, C, replace=False)).astype(np.int32))
    anc = jnp.asarray(rng.integers(-1, 1 << 20, (C, D)).astype(np.int32))
    q = jnp.asarray(rng.integers(0, 1 << 20, n).astype(np.int32))
    got = ops.closure_expand(q, sorted_ids, anc)
    want = ref.ref_closure_expand(q, sorted_ids, anc)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("T,N", [(0, 5), (300, 7), (2048, 2048), (5000, 1300)])
@pytest.mark.parametrize("hi_space", [3, 50])  # duplicate-density sweep
def test_pair_search_windowed_matches_resident(T, N, hi_space, rng):
    """The pair search must equal the numpy searchsorted oracle bit-exactly
    ('left' contract) at any table/query size, empty tables included."""
    hi = np.sort(rng.integers(0, hi_space, T).astype(np.int32))
    lo = rng.integers(0, 1000, T).astype(np.int32)
    order = np.lexsort((lo, hi))
    hi, lo = hi[order], lo[order]
    qh = rng.integers(0, hi_space + 2, N).astype(np.int32)
    ql = rng.integers(-5, 1005, N).astype(np.int32)
    off = np.int64(np.iinfo(np.int32).min)
    key = hi.astype(np.int64) * (1 << 32) + (lo.astype(np.int64) - off)
    qkey = qh.astype(np.int64) * (1 << 32) + (ql.astype(np.int64) - off)
    want = np.searchsorted(key, qkey, side="left")
    got = np.asarray(ops.pair_search(
        jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(qh), jnp.asarray(ql)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n", [1, 100, 512, 1000, 5000])
@pytest.mark.parametrize("block", [1024, 2048])
@pytest.mark.parametrize("density", [0.0, 0.3, 1.0])
def test_stream_compact_sweep(n, block, density, rng):
    from repro.kernels.stream_compact import stream_compact_pallas

    mask = jnp.asarray(rng.random(n) < density)
    padded = ops._pad1(mask.astype(jnp.int32), block, np.int32(0))
    loc, cnt = stream_compact_pallas(padded, block=block, interpret=True)
    rloc, rcnt = ref.ref_stream_compact(padded, block)
    np.testing.assert_array_equal(np.asarray(loc), np.asarray(rloc))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))
    # assembled wrapper == flatnonzero prefix
    want = np.flatnonzero(np.asarray(mask))
    for cap in (8, 256, 1 << 13):
        take, ok, total = ops.compact_indices(mask, cap, block=block)
        assert int(total) == len(want)
        np.testing.assert_array_equal(np.asarray(take)[np.asarray(ok)],
                                      want[:cap])


@pytest.mark.parametrize("n", [5, 513, 4096])
def test_interval_compact_fused(n, rng):
    p = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
    o = jnp.asarray(rng.integers(0, 1 << 20, n), jnp.int32)
    params = jnp.asarray([10, 40, 0, 1 << 19], jnp.int32)
    want = np.flatnonzero(np.asarray(
        ref.ref_interval_filter(None, p, o, 10, 40, 0, 1 << 19, 0)))
    take, ok, total = ops.interval_compact(p, o, params, 256)
    assert int(total) == len(want)
    np.testing.assert_array_equal(np.asarray(take)[np.asarray(ok)], want[:256])


@pytest.mark.parametrize("n", [5, 513, 4096])
@pytest.mark.parametrize("density", [0.0, 0.5, 1.0])
def test_masked_interval_compact_fused(n, density, rng):
    """Tombstone-aware fused compaction == interval predicate AND liveness."""
    p = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
    o = jnp.asarray(rng.integers(0, 1 << 20, n), jnp.int32)
    alive = jnp.asarray(rng.random(n) < density)
    params = jnp.asarray([10, 40, 0, 1 << 19], jnp.int32)
    want = np.flatnonzero(np.asarray(
        ref.ref_interval_filter(None, p, o, 10, 40, 0, 1 << 19, 0))
        & np.asarray(alive))
    take, ok, total = ops.masked_interval_compact(p, o, alive, params, 256)
    assert int(total) == len(want)
    np.testing.assert_array_equal(np.asarray(take)[np.asarray(ok)], want[:256])


def _mask_pattern(rng, n, density, pattern):
    """0/1 int32 mask: iid bits, long runs, or bits clustered at one end."""
    if pattern == "runs":  # runs of 1..300 equal bits
        bits, val = [], rng.random() < density
        while len(bits) < n:
            bits += [val] * int(rng.integers(1, 300))
            val = rng.random() < density
        return np.asarray(bits[:n], np.int32)
    m = rng.random(n) < density
    if pattern == "tail":  # every match in the highest slots
        m = np.sort(m)
    return m.astype(np.int32)


@pytest.mark.parametrize("block", [1024, 2048, 4096])
@pytest.mark.parametrize("pattern", ["iid", "runs", "tail"])
@pytest.mark.parametrize("density", [0.0, 0.13, 1.0])
def test_stream_compact_chunked_sweep(block, pattern, density, rng):
    """Shift-network body == ref across block x mask shape x density.

    Long runs and tail-clustered matches drive the largest displacements
    (every shift step fires); density 0 and 1 are the empty-output and
    all-survivors edges, where no match moves or every slot fills.
    """
    from repro.kernels.stream_compact import stream_compact_pallas

    n = block * 2 + block // 2  # partial final tile after padding
    mask = jnp.asarray(_mask_pattern(rng, n, density, pattern))
    padded = ops._pad1(mask, block, np.int32(0))
    loc, cnt = stream_compact_pallas(padded, block=block, interpret=True)
    rloc, rcnt = ref.ref_stream_compact(padded, block)
    np.testing.assert_array_equal(np.asarray(loc), np.asarray(rloc))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))


@pytest.mark.parametrize("block", [1024, 4096])
@pytest.mark.parametrize("n", [100, 5000, 9000])
def test_compact_indices_large_blocks(block, n, rng):
    """The assembled wrapper is block-size invariant (4096 == 1024 == ref)."""
    mask = jnp.asarray(rng.random(n) < 0.2)
    want = np.flatnonzero(np.asarray(mask))
    for cap in (8, 1 << 13):
        take, ok, total = ops.compact_indices(mask, cap, block=block)
        assert int(total) == len(want)
        np.testing.assert_array_equal(np.asarray(take)[np.asarray(ok)],
                                      want[:cap])


@pytest.mark.parametrize("block", [1024, 4096])
@pytest.mark.parametrize("n", [513, 5000])
@pytest.mark.parametrize("density", [0.0, 0.4, 1.0])
def test_masked_interval_compact_block_sweep(n, block, density, rng):
    """Fused masked variant parity across the new block sizes."""
    p = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
    o = jnp.asarray(rng.integers(0, 1 << 20, n), jnp.int32)
    alive = jnp.asarray(rng.random(n) < density)
    params = jnp.asarray([10, 40, 0, 1 << 19], jnp.int32)
    want = np.flatnonzero(np.asarray(
        ref.ref_interval_filter(None, p, o, 10, 40, 0, 1 << 19, 0))
        & np.asarray(alive))
    take, ok, total = ops.masked_interval_compact(p, o, alive, params, 256,
                                                  block=block)
    assert int(total) == len(want)
    np.testing.assert_array_equal(np.asarray(take)[np.asarray(ok)],
                                  want[:256])


@pytest.mark.parametrize("block", [1024, 2048, 4096])
@pytest.mark.parametrize("da,db", [(0.0, 0.0), (0.2, 0.9), (1.0, 1.0),
                                   (0.0, 1.0)])
def test_dual_compact_sweep(block, da, db, rng):
    """Dual-mask kernel: both streams == ref, one grid pass.

    Covers asymmetric densities and the empty-output / all-survivors edges
    on each stream independently.
    """
    from repro.kernels.stream_compact import dual_compact_pallas

    n = block * 2
    ma = jnp.asarray((rng.random(n) < da).astype(np.int32))
    mb = jnp.asarray((rng.random(n) < db).astype(np.int32))
    la, ca, lb, cb = dual_compact_pallas(ma, mb, block=block, interpret=True)
    rla, rca, rlb, rcb = ref.ref_dual_compact(ma, mb, block)
    for got, want in ((la, rla), (ca, rca), (lb, rlb), (cb, rcb)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_dual_compact_indices_wrapper(rng):
    """ops.dual_compact_indices == two compact_indices, one kernel pass."""
    n = 3000
    ma = jnp.asarray(rng.random(n) < 0.15)
    mb = jnp.asarray(rng.random(n) < 0.6)
    wa, wb = np.flatnonzero(np.asarray(ma)), np.flatnonzero(np.asarray(mb))
    for cap in (16, 1 << 12):
        ta, oka, tota, tb, okb, totb = ops.dual_compact_indices(
            ma, mb, cap)
        assert int(tota) == len(wa) and int(totb) == len(wb)
        np.testing.assert_array_equal(np.asarray(ta)[np.asarray(oka)],
                                      wa[:cap])
        np.testing.assert_array_equal(np.asarray(tb)[np.asarray(okb)],
                                      wb[:cap])


@given(st.integers(1, 6000), st.integers(0, 2**31 - 2),
       st.sampled_from([1024, 2048, 4096]),
       st.sampled_from(["iid", "runs", "tail"]))
@settings(max_examples=20, deadline=None)
def test_stream_compact_chunked_property(n, seed, block, pattern):
    from repro.kernels.stream_compact import stream_compact_pallas

    rng = np.random.default_rng(seed)
    mask = jnp.asarray(_mask_pattern(rng, n, rng.random(), pattern))
    padded = ops._pad1(mask, block, np.int32(0))
    loc, cnt = stream_compact_pallas(padded, block=block, interpret=True)
    rloc, rcnt = ref.ref_stream_compact(padded, block)
    np.testing.assert_array_equal(np.asarray(loc), np.asarray(rloc))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))


@pytest.mark.parametrize("block", [512, 1536, 1 << 16])
def test_stream_compact_rejects_untiled_block(block):
    """Tiles are whole (8, 128) int32 vreg tiles, at most 2**15 rows: any
    other block is refused on every backend, not only by the TPU compiler."""
    from repro.kernels.stream_compact import stream_compact_pallas

    with pytest.raises(ValueError, match="block"):
        stream_compact_pallas(jnp.zeros((1 << 16,), jnp.int32), block=block,
                              interpret=True)


def _sorted_pair_run(rng, n, key_space):
    """Random (hi, lo)-lex-sorted int32 run; small key_space → dense dups."""
    hi = rng.integers(0, key_space, n).astype(np.int32)
    lo = rng.integers(0, key_space, n).astype(np.int32)
    k = np.lexsort((lo, hi))
    return hi[k], lo[k]


@pytest.mark.parametrize("n,m", [(1, 1), (7, 100), (513, 513), (2048, 31),
                                 (1, 2000), (1000, 1000)])
@pytest.mark.parametrize("key_space", [3, 1 << 20])  # dup density sweep
def test_merge_gather_sweep(n, m, key_space, rng):
    """Device merge == ref oracle across sizes × duplicate densities."""
    ah, al = _sorted_pair_run(rng, n, key_space)
    bh, bl = _sorted_pair_run(rng, m, key_space)
    args = tuple(map(jnp.asarray, (ah, al, bh, bl)))
    got = np.asarray(ops.merge_gather(*args))
    want = np.asarray(ref.ref_merge_sorted(*args))
    np.testing.assert_array_equal(got, want)
    # the map is a permutation and the gathered keys are sorted + stable
    assert len(np.unique(got)) == n + m
    mh = np.where(got < n, ah[np.clip(got, 0, n - 1)],
                  bh[np.clip(got - n, 0, m - 1)])
    ml = np.where(got < n, al[np.clip(got, 0, n - 1)],
                  bl[np.clip(got - n, 0, m - 1)])
    key = mh.astype(np.int64) << 32 | ml.astype(np.int64)
    assert (np.diff(key) >= 0).all()


@pytest.mark.parametrize("n,m", [(64, 16), (517, 100), (1500, 1500)])
@pytest.mark.parametrize("tombstone_ratio", [0.0, 0.3, 1.0])
def test_merge_gather_masked_compaction(n, m, tombstone_ratio, rng):
    """Merge-everything-then-compact == host merge of pre-filtered runs.

    The device compaction path (core/delta.py) merges runs WITH their dead
    rows and drops them through the stream-compaction kernel afterwards;
    a stable merge followed by a stable filter must equal the merge of the
    filtered runs — the contract this pins across tombstone ratios.
    """
    from repro.core.index import merge_sorted

    def rows_run(k):
        hi, lo = _sorted_pair_run(rng, k, 50)
        rows = np.stack([rng.integers(0, 1 << 20, k).astype(np.int32),
                         hi, lo], axis=1)
        alive = rng.random(k) >= tombstone_ratio
        key = hi.astype(np.int64) << 32 | lo.astype(np.int64)
        return rows, alive, key

    a_rows, a_alive, a_key = rows_run(n)
    b_rows, b_alive, b_key = rows_run(m)
    gidx = np.asarray(ops.merge_gather(
        *map(jnp.asarray, (a_rows[:, 1], a_rows[:, 2],
                           b_rows[:, 1], b_rows[:, 2]))))
    alive = np.asarray(ops.two_source_gather(
        jnp.asarray(a_alive), jnp.asarray(b_alive), jnp.asarray(gidx)))
    n_live = int(a_alive.sum() + b_alive.sum())
    take, ok, total = ops.compact_indices(jnp.asarray(alive), max(n_live, 8))
    src = np.asarray(take)[:n_live]
    got = np.asarray(ops.two_source_gather(
        jnp.asarray(a_rows), jnp.asarray(b_rows), jnp.asarray(gidx[src])))
    assert int(total) == n_live
    want, _ = merge_sorted(a_rows[a_alive], a_key[a_alive],
                           b_rows[b_alive], b_key[b_alive])
    np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize("n,m", [(256, 256), (300, 270), (1030, 5000),
                                 (4096, 256), (2000, 2000)])
@pytest.mark.parametrize("key_space", [3, 50, 1 << 20])  # dup density sweep
def test_merge_gather_partitioned_sweep(n, m, key_space, rng):
    """Device merge == ref oracle at multi-hundred-row runs x dup density.

    Long duplicate-key runs on both sides are where the stable
    A-before-B rule (B rows search with side='right') must hold.
    """
    ah, al = _sorted_pair_run(rng, n, key_space)
    bh, bl = _sorted_pair_run(rng, m, key_space)
    args = tuple(map(jnp.asarray, (ah, al, bh, bl)))
    got = np.asarray(ops.merge_gather(*args))
    want = np.asarray(ref.ref_merge_sorted(*args))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,m", [(1100, 1100), (1024, 4096)])
@pytest.mark.parametrize("tombstone_ratio", [0.0, 0.3, 1.0])
def test_merge_gather_partitioned_masked_compaction(n, m, tombstone_ratio,
                                                    rng):
    """Device merge + tombstone drop == host merge of filtered runs.

    The device-compaction contract (core/delta.py) re-pinned at runs past
    one compaction tile, across tombstone ratios including kill-everything.
    """
    from repro.core.index import merge_sorted

    def rows_run(k):
        hi, lo = _sorted_pair_run(rng, k, 50)
        rows = np.stack([rng.integers(0, 1 << 20, k).astype(np.int32),
                         hi, lo], axis=1)
        alive = rng.random(k) >= tombstone_ratio
        key = hi.astype(np.int64) << 32 | lo.astype(np.int64)
        return rows, alive, key

    a_rows, a_alive, a_key = rows_run(n)
    b_rows, b_alive, b_key = rows_run(m)
    ops.merge_gather.clear_cache()  # counters bump at trace time only
    ops.reset_pass_counters()
    gidx = np.asarray(ops.merge_gather(
        *map(jnp.asarray, (a_rows[:, 1], a_rows[:, 2],
                           b_rows[:, 1], b_rows[:, 2]))))
    assert ops.pass_counters["merge"] >= 1  # traced the device merge
    alive = np.asarray(ops.two_source_gather(
        jnp.asarray(a_alive), jnp.asarray(b_alive), jnp.asarray(gidx)))
    n_live = int(a_alive.sum() + b_alive.sum())
    take, ok, total = ops.compact_indices(jnp.asarray(alive), max(n_live, 8))
    src = np.asarray(take)[:n_live]
    got = np.asarray(ops.two_source_gather(
        jnp.asarray(a_rows), jnp.asarray(b_rows), jnp.asarray(gidx[src])))
    assert int(total) == n_live
    want, _ = merge_sorted(a_rows[a_alive], a_key[a_alive],
                           b_rows[b_alive], b_key[b_alive])
    np.testing.assert_array_equal(got, np.asarray(want))


@given(st.integers(256, 1200), st.integers(256, 1200),
       st.integers(0, 2**31 - 2))
@settings(max_examples=20, deadline=None)
def test_merge_gather_partitioned_property(n, m, seed):
    rng = np.random.default_rng(seed)
    ah, al = _sorted_pair_run(rng, n, int(rng.integers(2, 1 << 16)))
    bh, bl = _sorted_pair_run(rng, m, int(rng.integers(2, 1 << 16)))
    args = tuple(map(jnp.asarray, (ah, al, bh, bl)))
    got = np.asarray(ops.merge_gather(*args))
    np.testing.assert_array_equal(got, np.asarray(ref.ref_merge_sorted(*args)))


def test_two_source_gather_degenerate_sources(rng):
    """Empty base (fully-compacted-away store) and absent delta both work."""
    rows = jnp.asarray(rng.integers(0, 100, (16, 3)).astype(np.int32))
    idx = jnp.asarray(np.arange(16, dtype=np.int32))
    empty = jnp.zeros((0, 3), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.two_source_gather(empty, rows, idx)), np.asarray(rows))
    np.testing.assert_array_equal(
        np.asarray(ops.two_source_gather(rows, None, idx)), np.asarray(rows))
    np.testing.assert_array_equal(
        np.asarray(ops.two_source_gather(rows, empty, idx)), np.asarray(rows))


@given(st.integers(1, 300), st.integers(1, 300), st.integers(0, 2**31 - 2))
@settings(max_examples=25, deadline=None)
def test_merge_gather_property(n, m, seed):
    rng = np.random.default_rng(seed)
    ah, al = _sorted_pair_run(rng, n, int(rng.integers(2, 1 << 16)))
    bh, bl = _sorted_pair_run(rng, m, int(rng.integers(2, 1 << 16)))
    args = tuple(map(jnp.asarray, (ah, al, bh, bl)))
    np.testing.assert_array_equal(
        np.asarray(ops.merge_gather(*args)),
        np.asarray(ref.ref_merge_sorted(*args)))


@given(st.integers(1, 200), st.integers(1, 300), st.integers(0, 2**31 - 2))
@settings(max_examples=25, deadline=None)
def test_pair_search_property(T, n, seed):
    rng = np.random.default_rng(seed)
    fps = np.sort(rng.choice(1 << 50, T, replace=False))
    thi = jnp.asarray((fps >> 31).astype(np.int32))
    tlo = jnp.asarray((fps & ((1 << 31) - 1)).astype(np.int32))
    qs = rng.choice(1 << 50, n)
    qhi = jnp.asarray((qs >> 31).astype(np.int32))
    qlo = jnp.asarray((qs & ((1 << 31) - 1)).astype(np.int32))
    got = np.asarray(ops.pair_search(thi, tlo, qhi, qlo))
    want = np.searchsorted(fps, qs, side="left")
    np.testing.assert_array_equal(got, want)
