"""Octree-based Islandization (paper §IV-A) — TPU-native implementation.

Partition the sampled point cloud (central points) into *Islands* of
spatially adjacent point subsets:

  Step 1  select Hub points among the sampled centers (random, as in the
          paper's Partitioning Module; FPS optional for better coverage);
  Step 2  round-based gathering of adjacent Sampled-Octree nodes around
          every hub (multi-source BFS over occupied voxels,
          26-connectivity).  A node reached in an earlier round is "nearer"
          (paper rule); same-round ties go to the hub with the smallest
          euclidean distance to the voxel center;
  Step 3  islands = point subsets whose centers share a Hub List — every
          center lands in exactly ONE island (partition property);
  Step 4  Island Lists: hub subset first, then BFS-round order (the paper's
          inside-to-outside processing order), padded to a fixed capacity.

All steps are jittable with static shapes.  Voxels are nodes of the linear
Sampled Octree at ``level`` (so "adjacent octree node" == adjacent occupied
voxel).  Centers whose island is already at capacity overflow into
``solo_centers`` and are processed without reuse (mirrors fixed hardware
capacity; counted honestly by the workload model).

Implementation detail vs. the paper: if the occupied-voxel graph is
disconnected and BFS saturates before every voxel is reached, remaining
voxels are assigned to the globally nearest hub (the paper's stopping rule
"until every central point belongs to a Hub List" assumes connectivity).

The BFS runs on a dense voxel grid of the octree level with one cell of
margin, where each of the 27 neighbours is a static shift of the flat
grid, so no op in the loop indexes by data.  A round costs
O(27 * 8**level) elementwise work, independent of S: cheap at level 4
(5,832 cells), but a scene-scale configuration at level >= 7 would have
to revisit it.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from . import morton
from .sampling import farthest_point_sampling, index_uniform

UINT32_SENTINEL = jnp.uint32(0xFFFFFFFF)


@jax.tree_util.register_pytree_node_class
@dataclass
class Islands:
    """Result of islandization.

    members:  (H, M) int32 — center (subset) indices per island, hub at
              slot 0, -1 padding.  A center appears in at most one island.
    hub:      (H,) int32 — hub center index per island (== members[:, 0]).
    solo:     (S,) bool — centers that overflowed island capacity; processed
              without reuse.
    round_of: (S,) int32 — BFS round at which each center's voxel was
              gathered (0 = hub's own voxel).
    """
    members: jnp.ndarray
    hub: jnp.ndarray
    solo: jnp.ndarray
    round_of: jnp.ndarray

    def tree_flatten(self):
        return (self.members, self.hub, self.solo, self.round_of), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_islands(self) -> int:
        return self.members.shape[0]

    @property
    def capacity(self) -> int:
        return self.members.shape[1]


@partial(jax.jit,
         static_argnames=("n_hubs", "level", "capacity", "hub_select",
                          "max_rounds"))
def islandize(centers: jnp.ndarray, n_hubs: int, *, level: int = 4,
              capacity: int = 64, hub_select: str = "random",
              max_rounds: int = 32,
              key: jax.Array | None = None,
              center_valid: jnp.ndarray | None = None,
              n_hubs_valid=None) -> Islands:
    """Partition ``centers`` (S, 3) into ``n_hubs`` islands.

    ``capacity`` = max subsets per island (paper default: 32; we default to
    2x for headroom).  Returns :class:`Islands`.

    Ragged-batch contract: ``center_valid`` (S,) bool marks padding
    centers — they occupy no voxel, join no island and are never solo, so
    islands, schedules and workload counters on a padded cloud are
    identical to the unpadded run.  ``n_hubs_valid`` (traced count <=
    ``n_hubs``) keeps hub slots beyond the valid-center budget inert
    (no BFS seed, excluded from the nearest-hub fallback): a padded cloud
    grows exactly as many islands as its unpadded twin, with the
    remaining rows of ``members`` empty.  Hub selection is shape-stable
    (per-index scores / masked FPS), so the first ``n_hubs_valid`` hubs
    match the unpadded run's hubs one for one.
    """
    S = centers.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    hub_ok = (None if n_hubs_valid is None
              else jnp.arange(n_hubs) < n_hubs_valid)

    # ---- voxelization of the Sampled Octree at `level` -------------------
    clo, chi = morton.masked_bounds(centers, center_valid)
    codes = morton.morton_codes(centers, morton.MAX_DEPTH, lo=clo, hi=chi)
    ckeys = morton.node_key(codes, level, morton.MAX_DEPTH)        # (S,)
    if center_valid is not None:
        # padding centers never occupy a voxel
        ckeys = jnp.where(center_valid, ckeys, UINT32_SENTINEL)

    # unique occupied voxels, padded to S with UINT32_SENTINEL sentinels
    sort_keys = jnp.sort(ckeys)
    is_new = jnp.concatenate([jnp.array([True]),
                              sort_keys[1:] != sort_keys[:-1]])
    # unique keys compacted to the front, UINT32_SENTINEL sentinel padding
    # (codes are 63-bit so the sentinel can never collide with a real key)
    ukeys = jnp.sort(jnp.where(is_new, sort_keys, UINT32_SENTINEL))

    vox_of_center = jnp.searchsorted(ukeys, ckeys).astype(jnp.int32)  # (S,)

    # voxel center coordinates (for same-round nearest-hub tie-break)
    side = 1 << level
    valid_vox = ukeys != UINT32_SENTINEL
    ivox = morton.decode(jnp.where(valid_vox, ukeys, jnp.uint32(0)))
    vxyz = ivox.astype(jnp.float32)
    extent = jnp.maximum(jnp.max(chi - clo), 1e-9)
    vcenter = clo + (vxyz + 0.5) / side * extent                     # (S, 3)

    # ---- Step 1: hub selection -------------------------------------------
    if hub_select == "fps":
        hub_idx = farthest_point_sampling(centers, n_hubs,
                                          valid=center_valid)
    else:  # random (paper default), via shape-stable per-index scores so
        # a padded cloud selects the same hubs as its unpadded twin
        scores = index_uniform(key, S)
        if center_valid is not None:
            scores = jnp.where(center_valid, scores, jnp.inf)
        hub_idx = jnp.argsort(scores)[:n_hubs]
    hub_idx = hub_idx.astype(jnp.int32)                              # (H,)
    hub_xyz = centers[hub_idx]                                       # (H, 3)
    hub_vox = vox_of_center[hub_idx]                                 # (H,)
    # inert hub slots scatter out of bounds (dropped)
    hub_tgt = hub_vox if hub_ok is None else jnp.where(hub_ok, hub_vox, S)

    # ---- Step 2: multi-source BFS over occupied voxels ---------------
    INF = jnp.float32(jnp.inf)
    INT_MAX = jnp.iinfo(jnp.int32).max
    assign0 = jnp.full((S,), -1, jnp.int32)
    # seed: hub voxels (later hub wins ties on the same voxel — rare)
    assign0 = assign0.at[hub_tgt].set(jnp.arange(n_hubs, dtype=jnp.int32),
                                      mode="drop")

    # Dense grid of the octree level, one cell of margin on every side,
    # flattened x-major.  Neighbour (dx, dy, dz) of a cell is the cell at
    # flat offset dx*P*P + dy*P + dz.  Only the window [W, G - W) is
    # stored: it holds every inner cell, and every neighbour of one lies in
    # the grid.  Cells hold a hub id, UNSEEN (occupied, not yet reached)
    # or EMPTY (no voxel, or margin: never a frontier, never reached).
    P = side + 2
    G = P ** 3
    W = P * P + P + 1
    n = G - 2 * W
    UNSEEN, EMPTY = -1, -2
    # voxel (x, y, z) -> grid cell (x+1, y+1, z+1), at x*P*P + y*P + z in
    # the window; padding voxels scatter out of bounds (dropped)
    cell = jnp.sum(ivox.astype(jnp.int32) * jnp.array([P * P, P, 1]), -1)
    cell = jnp.where(valid_vox, cell, n)
    g_ass = jnp.full((n,), EMPTY, jnp.int32).at[cell].set(assign0,
                                                          mode="drop")
    g_rnd = jnp.where(g_ass >= 0, 0, INT_MAX)
    # per-axis channels: seed hubs' xyz, voxel centers
    g_hub = list(hub_xyz[jnp.clip(g_ass, 0, n_hubs - 1)].T)
    g_vc = list(jnp.zeros((n, 3), jnp.float32).at[cell].set(
        vcenter, mode="drop").T)
    # 27-neighbourhood in adjacent_node_keys' order (meshgrid "ij")
    shifts = [dx * P * P + dy * P + dz
              for dx in (-1, 0, 1) for dy in (-1, 0, 1) for dz in (-1, 0, 1)]

    def bfs_round(r, state):
        ass, rnd, hx, hy, hz = state
        padded = [jnp.pad(a, W, constant_values=c)
                  for a, c in ((ass, EMPTY), (hx, 0.), (hy, 0.), (hz, 0.))]
        best_d = jnp.full((n,), INF)
        best = [jnp.full((n,), EMPTY, jnp.int32)] + [jnp.zeros((n,))] * 3
        for o in shifts:
            nass, nx, ny, nz = (jax.lax.slice_in_dim(a, W + o, W + o + n)
                                for a in padded)
            # a cell gathered in an earlier round is a frontier; the
            # distance from its hub to this voxel's center (refcore.sq3)
            dx, dy, dz = nx - g_vc[0], ny - g_vc[1], nz - g_vc[2]
            d = (dx * dx + dy * dy) + dz * dz
            # strict "<" keeps the first of equal slots, as argmin does
            win = (nass >= 0) & (d < best_d)
            best_d = jnp.where(win, d, best_d)
            best = [jnp.where(win, c, b)
                    for c, b in zip((nass, nx, ny, nz), best)]
        reach = (best_d < INF) & (ass == UNSEEN)
        ass, hx, hy, hz = (jnp.where(reach, b, a)
                           for b, a in zip(best, (ass, hx, hy, hz)))
        rnd = jnp.where(reach, r, rnd)
        return ass, rnd, hx, hy, hz

    g_ass, g_rnd, *_ = jax.lax.fori_loop(1, max_rounds + 1, bfs_round,
                                         (g_ass, g_rnd, *g_hub))
    assign = g_ass.at[cell].get(mode="fill", fill_value=-1)
    vrnd = g_rnd.at[cell].get(mode="fill", fill_value=INT_MAX)

    # fallback: disconnected voxels -> globally nearest (real) hub
    unassigned = (assign < 0) & valid_vox
    d_all = jnp.sum((vcenter[:, None, :] - hub_xyz[None, :, :]) ** 2, -1)
    if hub_ok is not None:
        d_all = jnp.where(hub_ok[None, :], d_all, INF)
    nearest = jnp.argmin(d_all, axis=-1).astype(jnp.int32)
    assign = jnp.where(unassigned, nearest, assign)
    vrnd = jnp.where(unassigned, max_rounds + 1, vrnd)

    # ---- Step 3: per-center island id ------------------------------------
    island_of = assign[vox_of_center]                                # (S,)
    round_of = vrnd[vox_of_center].astype(jnp.int32)                 # (S,)
    if center_valid is not None:
        # padding centers route to the drop row of the member scatter
        island_of = jnp.where(center_valid, island_of, n_hubs)

    # ---- Step 4: Island Lists (hub first, then round order) --------------
    d_to_hub = jnp.sum((centers - hub_xyz[jnp.clip(island_of, 0, n_hubs - 1)]
                        ) ** 2, -1)
    hub_idx_tgt = hub_idx if hub_ok is None else jnp.where(hub_ok, hub_idx, S)
    is_hub = jnp.zeros((S,), bool).at[hub_idx_tgt].set(True, mode="drop")
    # sort key: (island, hub-first, round, distance)
    ordr = jnp.lexsort((d_to_hub, round_of.astype(jnp.float32),
                        (~is_hub).astype(jnp.int32), island_of))
    # rank within island
    sorted_isl = island_of[ordr]
    pos_in_isl = jnp.arange(S) - jnp.searchsorted(sorted_isl, sorted_isl)
    M = capacity
    fits = pos_in_isl < M
    members = jnp.full((n_hubs, M), -1, jnp.int32)
    # overflow entries are routed to row n_hubs (out of bounds -> dropped)
    members = members.at[jnp.where(fits, sorted_isl, n_hubs),
                         jnp.clip(pos_in_isl, 0, M - 1)].set(
        ordr.astype(jnp.int32), mode="drop")
    solo = jnp.zeros((S,), bool).at[ordr].set(~fits)
    if center_valid is not None:
        # padding centers are neither members nor solo
        solo &= center_valid

    return Islands(members=members, hub=hub_idx, solo=solo,
                   round_of=round_of)
