"""Kernel microbenches: Pallas (interpret on CPU) vs jnp reference.

NOTE: on this CPU host the Pallas kernels run in INTERPRET mode, so their
wall-times measure the validation path, not TPU performance — the numbers
that matter are the ref-path times (XLA CPU) and, on real hardware, the
Mosaic-compiled kernels.  Reported for completeness + regression tracking.

The SIZE SWEEP section (1e5 -> 4e6 rows, REPRO_BENCH_SWEEP_MAX tunable)
captures the scaling curve of stream compaction and the compaction-merge
(device merge + tombstone compaction) at multi-million-row stores.
``kernels/sweep/scale_ok`` gates on the sweep actually reaching >= 2e6
rows.
"""
from __future__ import annotations

import os

import numpy as np

SWEEP_SIZES = (100_000, 400_000, 1_000_000, 2_000_000, 4_000_000)


def _sweep(emit, timeit):
    import jax.numpy as jnp

    from repro.kernels import ops

    max_n = int(float(os.environ.get("REPRO_BENCH_SWEEP_MAX", "4e6")))
    sizes = [n for n in SWEEP_SIZES if n <= max_n]
    rng = np.random.default_rng(7)
    ran = 0
    for n in sizes:
        mask = jnp.asarray(rng.random(n) < 0.1)
        p = jnp.asarray(rng.integers(0, 1000, n), jnp.int32)
        o = jnp.asarray(rng.integers(0, 1 << 20, n), jnp.int32)
        alive = jnp.asarray(rng.random(n) < 0.97)
        params = jnp.asarray([100, 300, 0, 1 << 19], jnp.int32)
        cap = 1 << 15
        blk = ops.auto_block(n)
        t, _ = timeit(lambda: ops.compact_indices(mask, cap, block=blk),
                      repeats=2)
        emit(f"kernels/sweep/stream_compact_n{n}", t, n=n, block=blk,
             rows_per_s=int(n / max(t, 1e-9)))
        t, _ = timeit(lambda: ops.masked_interval_compact(
            p, o, alive, params, cap, block=blk), repeats=2)
        emit(f"kernels/sweep/masked_interval_compact_n{n}", t, n=n, block=blk,
             rows_per_s=int(n / max(t, 1e-9)))

        # compaction-merge: fold a 10% delta into a 90% base (tombstones
        # dropped through the compaction kernel) — core/delta.py's device
        # compaction at scale
        nb, nd = (n * 9) // 10, n // 10
        def run(k):
            hi = rng.integers(0, 1 << 20, k).astype(np.int32)
            lo = rng.integers(0, 1 << 20, k).astype(np.int32)
            srt = np.lexsort((lo, hi))
            return jnp.asarray(hi[srt]), jnp.asarray(lo[srt])
        bh_, bl_ = run(nb)
        dh_, dl_ = run(nd)
        keep = jnp.asarray(rng.random(n) < 0.97)

        def merge_compact():
            gidx = ops.merge_gather(bh_, bl_, dh_, dl_)
            al = keep[gidx]
            return ops.compact_indices(al, cap, block=blk)

        t, _ = timeit(merge_compact, repeats=2)
        emit(f"kernels/sweep/merge_compact_n{n}", t, n=n,
             rows_per_s=int(n / max(t, 1e-9)))
        ran = n

    # block-size effect at a fixed size: the smallest tile vs 4096 tiles
    n = min(400_000, max_n)
    mask = jnp.asarray(rng.random(n) < 0.1)
    for blk in (ops.auto_block(0), ops.LARGE_BLOCK):
        t, _ = timeit(lambda: ops.compact_indices(mask, 1 << 15, block=blk),
                      repeats=2)
        emit(f"kernels/sweep/stream_compact_block{blk}", t, n=n, block=blk)

    emit("kernels/sweep/scale_ok", 0.0, max_rows=ran,
         passed=bool(ran >= 2_000_000))


def main():
    import jax.numpy as jnp

    from benchmarks.common import emit, timeit
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    N = 200_000
    p = jnp.asarray(rng.integers(0, 1000, N), jnp.int32)
    o = jnp.asarray(rng.integers(0, 1 << 20, N), jnp.int32)
    params = jnp.asarray([100, 300, 0, 1 << 19], jnp.int32)
    t, _ = timeit(ops.interval_filter, p, o, params, repeats=3)
    emit("kernels/interval_filter_pallas", t, n=N)
    import jax

    reff = jax.jit(lambda: ref.ref_interval_filter(None, p, o, 100, 300, 0, 1 << 19, 0))
    t, _ = timeit(reff, repeats=3)
    emit("kernels/interval_filter_ref", t, n=N)

    G, K = 2048, 16
    conc = jnp.asarray(rng.integers(-1, 500, (G, K)).astype(np.int32))
    bounds = conc + jnp.asarray(rng.integers(1, 64, (G, K)).astype(np.int32))
    t, _ = timeit(ops.msc_select, conc, bounds, repeats=3)
    emit("kernels/msc_select_pallas", t, groups=G)
    reff = jax.jit(lambda: ref.ref_msc_select(conc, bounds))
    t, _ = timeit(reff, repeats=3)
    emit("kernels/msc_select_ref", t, groups=G)

    N2 = 200_000
    mask = jnp.asarray(rng.random(N2) < 0.1)
    cap = 1 << 15
    t, _ = timeit(ops.compact_indices, mask, cap, repeats=3)
    emit("kernels/stream_compact_pallas", t, n=N2, cap=cap)
    t, _ = timeit(ops.interval_compact, p, o, params, cap, repeats=3)
    emit("kernels/interval_compact_fused_pallas", t, n=N, cap=cap)
    argsort_ref = jax.jit(lambda: jnp.argsort(~mask, stable=True)[:cap])
    t, _ = timeit(argsort_ref, repeats=3)
    emit("kernels/compact_argsort_ref", t, n=N2, cap=cap)

    _sweep(emit, timeit)


if __name__ == "__main__":
    main()
