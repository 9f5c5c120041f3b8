"""Hub-based Scheduling (paper §IV-B) — overlap detection + Hub Cache.

The FPGA processes an island temporally: hub subset first (fills the first K
Hub-Cache entries), then the remaining subsets in island-list order; each new
subset's points are probed against the dynamically updated Hub Octree
(hit -> reuse cached MLP result with delta compensation, miss -> compute,
insert into cache while capacity remains; no replacement within an island).

The TPU-native equivalent computes the *final cache contents and hit pattern
in closed form* (DESIGN.md §2): for the island's flattened point sequence
(subsets in island-list order, hub first) we mark first occurrences, assign
cache slots to the first ``cache_capacity`` distinct points in order, and
derive a ``reuse_slot`` map for every (subset, k) position.  Point identity
is the index into the input cloud — semantically identical to the paper's
Morton-code Hub-Octree probe (tests/test_hub_schedule.py checks the
equivalence against ``octree.contains``).

Every step is an equality compare of ids fed straight into a reduction over
the island's positions, with no sort, scatter or per-element gather:

1. a position is a first occurrence when it is live (id >= 0) and no
   earlier position holds its id (compare, any-reduce);
2. first occurrences take slots by a cumulative sum, and those below
   ``cache_capacity`` are cached;
3. a position's ``reuse_slot`` is the slot of the cached position holding
   its id, else -1 (compare, max-reduce);
4. slot c of the pool holds the id of the cached position given slot c
   (one-hot compare against the slots, max-reduce).

Each compare is fused into its reduction by XLA, so no (M·K)² array is
materialised; the only gather left reads the subsets' rows of ``nbr_idx``.

Results in the pool are stored *relative to the hub center* so every
non-hub subset needs exactly one compensation delta (c_hub - c_subset),
matching the paper's one-Δ-per-subset FCU dataflow.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from .islandize import Islands


@jax.tree_util.register_pytree_node_class
@dataclass
class Schedule:
    """Per-island reuse schedule (all arrays island-major).

    pool_ids:   (H, C) int32 — point ids resident in the Hub Cache at end of
                island (-1 = empty slot).  Slots 0..K-1 are the hub subset
                (paper: "first 32 entries").
    reuse_slot: (H, M, K) int32 — cache slot serving this position, or -1
                (position must be computed locally: cache overflow).
    is_first:   (H, M, K) bool — position is the first occurrence of its
                point in the island sequence (it *fills* its slot rather
                than hitting it; FLOP-counted as a compute, not a reuse).
    subset_valid: (H, M) bool — island-list row is a real subset.
    pos_live:   (H, M, K) bool — position holds a real gathered point (the
                row is a real subset AND the neighbor slot was filled with
                a valid point, i.e. its id >= 0).  Padding rows and
                unfillable ragged-batch slots are False: they never occupy
                cache slots, are never computed, and are excluded from
                workload counters.
    """
    pool_ids: jnp.ndarray
    reuse_slot: jnp.ndarray
    is_first: jnp.ndarray
    subset_valid: jnp.ndarray
    pos_live: jnp.ndarray

    def tree_flatten(self):
        return ((self.pool_ids, self.reuse_slot, self.is_first,
                 self.subset_valid, self.pos_live), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@partial(jax.jit, static_argnames=("cache_capacity",))
def build_schedule(islands: Islands, nbr_idx: jnp.ndarray,
                   cache_capacity: int) -> Schedule:
    """Derive the Hub-Cache schedule for every island.

    nbr_idx: (S, K) int32 — gathered point ids per subset (DSU output).
    cache_capacity: C — Hub-Cache entries (paper default 2x subset size).
    """
    H, M = islands.members.shape
    K = nbr_idx.shape[1]
    C = cache_capacity

    members = islands.members                                     # (H, M)
    valid_row = members >= 0
    safe_members = jnp.clip(members, 0, nbr_idx.shape[0] - 1)
    ids = nbr_idx[safe_members]                                   # (H, M, K)
    ids = jnp.where(valid_row[..., None], ids, -1)

    def per_island(ids_hmk):
        """ids_hmk: (M, K) -> schedule slices for one island."""
        flat = ids_hmk.reshape(-1)                                # (M*K,)
        n = flat.shape[0]
        seq = jnp.arange(n, dtype=jnp.int32)
        # invalid positions (padding) never occupy or hit slots
        live = flat >= 0
        # (q, p) pairs: row q is the earlier candidate, column p the probe;
        # each compare feeds its reduce over q and is never materialised
        same = flat[:, None] == flat[None, :]
        # first occurrence: no earlier position holds the same id
        seen = jnp.any(same & (seq[:, None] < seq[None, :]), axis=0)
        is_first = live & ~seen
        # slot of a first occurrence: rank among them in sequence order
        slot_of_pos = jnp.where(is_first, jnp.cumsum(is_first) - 1, -1)
        cached = is_first & (slot_of_pos < C)
        # per position: slot of the cached first occurrence of its id
        # (a padding position's -1 matches no cached, hence live, id)
        reuse = jnp.max(jnp.where(same & cached[:, None],
                                  slot_of_pos[:, None], -1), axis=0)
        # pool contents: id of the cached first occurrence given each slot
        in_slot = cached[None, :] & (
            slot_of_pos[None, :] == jnp.arange(C, dtype=jnp.int32)[:, None])
        pool = jnp.max(jnp.where(in_slot, flat[None, :], -1), axis=1)
        return (pool.astype(jnp.int32), reuse.reshape(M, K).astype(jnp.int32),
                is_first.reshape(M, K))

    pool, reuse, first = jax.vmap(per_island)(ids)
    return Schedule(pool_ids=pool, reuse_slot=reuse, is_first=first,
                    subset_valid=valid_row, pos_live=ids >= 0)
