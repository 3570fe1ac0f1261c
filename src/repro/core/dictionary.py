"""Distributed dictionary encoding (the paper's §III.B, in JAX).

The paper's Spark algorithm:

  1. partition the dataset; each partition extracts its distinct new terms,
  2. the driver sums per-partition distinct counts into disjoint id ranges
     (an exclusive prefix sum),
  3. each partition assigns ids within its range,
  4. the dataset is re-encoded via joins against the resulting map
     (broadcast when small, partitioned when large).

We keep that exact structure.  Single-shard build = sort + adjacent-unique +
cumsum (rank == id offset).  Multi-shard build (``sharded_dictionary_fn``) =
hash-partition terms with ``all_to_all`` so each distinct term has one owner
shard, then the per-shard counts + ``all_gather``-prefix-sum reproduce steps
2–3; lookups route queries to owners with the same pattern.

All device keys are (hi, lo) int32 fingerprint pairs (utils/pair64.py);
``extract`` resolves fp -> string on the host, mirroring the paper's
driver-side string world.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.utils import pair64

SENTINEL = np.int32(np.iinfo(np.int32).max)  # > any real 30-bit hi word


@jax.tree_util.register_pytree_node_class
@dataclass
class TermTable:
    """Device dictionary: lex-sorted fp pairs -> int32 ids (+reverse view)."""

    fp_hi: jnp.ndarray  # int32[T], sorted (pairs with SENTINEL padding tail)
    fp_lo: jnp.ndarray
    ids: jnp.ndarray  # int32[T], -1 on padding rows
    rev_ids: jnp.ndarray  # int32[T] ids sorted ascending (padding: INT32_MAX)
    rev_hi: jnp.ndarray  # fp planes aligned with rev_ids
    rev_lo: jnp.ndarray
    count: jnp.ndarray  # int32 scalar: number of real entries

    def tree_flatten(self):
        return (
            (self.fp_hi, self.fp_lo, self.ids, self.rev_ids, self.rev_hi, self.rev_lo, self.count),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    def locate(self, qhi, qlo):
        """fp pairs -> (ids, hit_mask); -1 where absent."""
        return pair64.lookup_pair(self.fp_hi, self.fp_lo, self.ids, qhi, qlo)

    def extract_fp(self, q_ids):
        """ids -> (fp_hi, fp_lo, hit_mask)."""
        pos = jnp.searchsorted(self.rev_ids, q_ids)
        pos_c = jnp.clip(pos, 0, self.rev_ids.shape[0] - 1)
        hit = self.rev_ids[pos_c] == q_ids
        return (
            jnp.where(hit, self.rev_hi[pos_c], -1),
            jnp.where(hit, self.rev_lo[pos_c], -1),
            hit,
        )


def table_from_host(fps: np.ndarray, ids: np.ndarray) -> TermTable:
    """Small host-built map (e.g. the TBox term map) -> TermTable."""
    hi, lo = pair64.split_np(fps)
    order = np.lexsort((lo, hi))
    hi, lo, ids = hi[order], lo[order], np.asarray(ids, dtype=np.int32)[order]
    rorder = np.argsort(ids, kind="stable")
    return TermTable(
        fp_hi=jnp.asarray(hi),
        fp_lo=jnp.asarray(lo),
        ids=jnp.asarray(ids),
        rev_ids=jnp.asarray(ids[rorder]),
        rev_hi=jnp.asarray(hi[rorder]),
        rev_lo=jnp.asarray(lo[rorder]),
        count=jnp.asarray(np.int32(len(ids))),
    )


def build_local_dictionary(hi, lo, valid, base):
    """Single-shard dictionary build (jit-safe, static shapes).

    ``(hi, lo)`` are term-occurrence fingerprints, ``valid`` masks real
    occurrences.  Returns a TermTable of size len(hi) (padding rows carry
    SENTINEL fps / -1 ids) whose ids are ``base + rank`` in fp order.
    """
    hi = jnp.where(valid, hi, SENTINEL)
    lo = jnp.where(valid, lo, SENTINEL)
    hi_s, lo_s = lax.sort((hi, lo), num_keys=2, is_stable=False)
    valid_s = hi_s != SENTINEL
    uniq = pair64.unique_mask_sorted(hi_s, lo_s) & valid_s
    ranks = jnp.cumsum(uniq.astype(jnp.int32)) - 1  # dup rows share their head's rank
    ids = jnp.where(valid_s, base + ranks, -1).astype(jnp.int32)
    count = uniq.astype(jnp.int32).sum()

    # compact unique rows to the front so the reverse view is dense in id
    # order (ids are assigned in fp order, so fp order == id order here).
    T = hi_s.shape[0]
    dest = jnp.where(uniq, ranks, T - 1)  # losers overwrite the scratch tail
    rev_hi = jnp.full((T,), SENTINEL, dtype=jnp.int32).at[dest].set(hi_s, mode="drop")
    rev_lo = jnp.full((T,), SENTINEL, dtype=jnp.int32).at[dest].set(lo_s, mode="drop")
    rev_ids = jnp.where(jnp.arange(T) < count, base + jnp.arange(T, dtype=jnp.int32), np.iinfo(np.int32).max)
    # fix scratch slot T-1 if it is real
    last_real = count > (T - 1)
    rev_hi = rev_hi.at[T - 1].set(jnp.where(last_real, rev_hi[T - 1], SENTINEL))
    rev_lo = rev_lo.at[T - 1].set(jnp.where(last_real, rev_lo[T - 1], SENTINEL))
    return TermTable(hi_s, lo_s, ids, rev_ids, rev_hi, rev_lo, count)


@jax.jit
def merge_tables(a: TermTable, b: TermTable) -> TermTable:
    """Union of two tables (disjoint key sets) -> one lex-sorted table."""
    hi = jnp.concatenate([a.fp_hi, b.fp_hi])
    lo = jnp.concatenate([a.fp_lo, b.fp_lo])
    ids = jnp.concatenate([a.ids, b.ids])
    hi_s, lo_s, perm = pair64.sort_pairs(hi, lo)
    ids_s = ids[perm]
    rev_ids = jnp.concatenate([a.rev_ids, b.rev_ids])
    rev_hi = jnp.concatenate([a.rev_hi, b.rev_hi])
    rev_lo = jnp.concatenate([a.rev_lo, b.rev_lo])
    rperm = jnp.argsort(rev_ids)
    return TermTable(
        hi_s, lo_s, ids_s,
        rev_ids[rperm], rev_hi[rperm], rev_lo[rperm],
        a.count + b.count,
    )


# ---------------------------------------------------------------------------
# Sharded build (shard_map body) — the paper's parallel algorithm proper
# ---------------------------------------------------------------------------


def _bin_by_owner(hi, lo, valid, n_shards: int, cap: int):
    """Scatter local terms into per-owner bins of static capacity ``cap``.

    Owner shard = fp mod n_shards (well-mixed fingerprints -> balanced).
    Returns (bins_hi, bins_lo) of shape (n_shards, cap) + overflow count.
    """
    owner = jnp.where(valid, (lo % n_shards).astype(jnp.int32), n_shards)
    # slot of each element within its owner bin = running count per owner
    one_hot = (owner[:, None] == jnp.arange(n_shards, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    slot = jnp.cumsum(one_hot, axis=0) - one_hot  # exclusive per-owner rank
    slot = (slot * one_hot).sum(axis=1)
    overflow = jnp.maximum(slot - (cap - 1), 0).sum()
    flat = jnp.clip(owner, 0, n_shards - 1) * cap + jnp.clip(slot, 0, cap - 1)
    keep = valid & (slot < cap)
    bins_hi = jnp.full((n_shards * cap,), SENTINEL, dtype=jnp.int32).at[
        jnp.where(keep, flat, n_shards * cap - 1)
    ].set(jnp.where(keep, hi, SENTINEL), mode="drop")
    bins_lo = jnp.full((n_shards * cap,), SENTINEL, dtype=jnp.int32).at[
        jnp.where(keep, flat, n_shards * cap - 1)
    ].set(jnp.where(keep, lo, SENTINEL), mode="drop")
    return bins_hi.reshape(n_shards, cap), bins_lo.reshape(n_shards, cap), overflow


def sharded_dictionary_fn(axis_name: str, n_shards: int, bin_cap: int, base: int):
    """Returns a shard_map-able body: local term columns -> (ids, table).

    Implements the paper's algorithm with one all_to_all each way:
      occurrences --(hash partition)--> owner shards --(unique+scan)-->
      id assignment --(reverse all_to_all)--> resolved occurrence ids.
    """

    def body(hi, lo, valid):
        # 1. route occurrences to owner shards (dedup happens at the owner)
        bins_hi, bins_lo, overflow = _bin_by_owner(hi, lo, valid, n_shards, bin_cap)
        recv_hi = lax.all_to_all(bins_hi, axis_name, 0, 0, tiled=False)
        recv_lo = lax.all_to_all(bins_lo, axis_name, 0, 0, tiled=False)
        rhi = recv_hi.reshape(-1)
        rlo = recv_lo.reshape(-1)

        # 2. local unique + global exclusive scan of counts (paper step 2)
        rhi_s, rlo_s = lax.sort((rhi, rlo), num_keys=2, is_stable=False)
        valid_s = rhi_s != SENTINEL
        uniq = pair64.unique_mask_sorted(rhi_s, rlo_s) & valid_s
        local_count = uniq.astype(jnp.int32).sum()
        counts = lax.all_gather(local_count, axis_name)
        my = lax.axis_index(axis_name)
        offset = jnp.where(jnp.arange(counts.shape[0]) < my, counts, 0).sum()

        # 3. assign ids in my disjoint range (paper step 3)
        ranks = jnp.cumsum(uniq.astype(jnp.int32)) - 1
        ids_s = jnp.where(valid_s, base + offset + ranks, -1).astype(jnp.int32)

        # 4. answer the original shards: lookup each routed bin in my table,
        #    then reverse the all_to_all to deliver ids to the askers.
        ans, _ = pair64.lookup_pair(rhi_s, rlo_s, ids_s, recv_hi.reshape(n_shards, -1), recv_lo.reshape(n_shards, -1))
        back = lax.all_to_all(ans, axis_name, 0, 0, tiled=False)  # (n_shards, cap)

        # 5. scatter bin answers back onto local occurrence order
        owner = jnp.where(valid, (lo % n_shards).astype(jnp.int32), n_shards)
        one_hot = (owner[:, None] == jnp.arange(n_shards, dtype=jnp.int32)[None, :]).astype(jnp.int32)
        slot = jnp.cumsum(one_hot, axis=0) - one_hot
        slot = (slot * one_hot).sum(axis=1)
        flat = jnp.clip(owner, 0, n_shards - 1) * bin_cap + jnp.clip(slot, 0, bin_cap - 1)
        occ_ids = jnp.where(valid & (slot < bin_cap), back.reshape(-1)[flat], -1)

        table = (
            rhi_s, rlo_s, ids_s,
            *_reverse_view(rhi_s, rlo_s, ids_s, uniq, local_count, base + offset),
        )
        # scalars leave shard_map as (1,)-vectors (one entry per shard)
        return occ_ids, table, overflow[None], local_count[None]

    return body


def sharded_out_specs():
    """out_specs matching sharded_dictionary_fn's outputs."""
    from jax.sharding import PartitionSpec as P

    d = P("d")
    return (d, (d,) * 6, d, d)


def _reverse_view(hi_s, lo_s, ids_s, uniq, count, base):
    T = hi_s.shape[0]
    ranks = jnp.cumsum(uniq.astype(jnp.int32)) - 1
    dest = jnp.where(uniq, ranks, T - 1)
    rev_hi = jnp.full((T,), SENTINEL, dtype=jnp.int32).at[dest].set(hi_s, mode="drop")
    rev_lo = jnp.full((T,), SENTINEL, dtype=jnp.int32).at[dest].set(lo_s, mode="drop")
    rev_ids = jnp.where(
        jnp.arange(T) < count, base + jnp.arange(T, dtype=jnp.int32), np.iinfo(np.int32).max
    )
    last_real = count > (T - 1)
    rev_hi = rev_hi.at[T - 1].set(jnp.where(last_real, rev_hi[T - 1], SENTINEL))
    rev_lo = rev_lo.at[T - 1].set(jnp.where(last_real, rev_lo[T - 1], SENTINEL))
    return rev_ids, rev_hi, rev_lo
