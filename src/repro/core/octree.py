"""Linear octree over Morton-sorted points (TPU-native octree-search engine).

The paper's hardware keeps the Input Octree / Sampled Octree / Hub Octrees in
BRAM and walks them with two pipelined Octree-Search Engines.  The linear
octree gives the same queries as array primitives:

  * ``node of point at depth d``      -> shift of its Morton code
  * ``points inside node``            -> contiguous slice of the sorted array
                                         found with two ``searchsorted``
  * ``membership test`` (Hub-Octree
    hit/miss of Overlap Detection)    -> ``searchsorted`` + equality check
  * ``adjacent nodes`` (Partitioning
    Module's round-based gathering)   -> decode key, +/-1 on each axis,
                                         re-encode (26-connectivity)

Everything is jittable; a numpy mirror lives in the analytics path.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from . import morton


@jax.tree_util.register_pytree_node_class
@dataclass
class LinearOctree:
    """Morton-sorted point index — the Input/Sampled Octree of the paper.

    Attributes:
      codes:  (N,) uint32 Morton codes, sorted ascending.
      order:  (N,) int32 permutation: codes[i] belongs to points[order[i]].
      depth:  quantization depth used for the codes.
    """
    codes: jnp.ndarray
    order: jnp.ndarray
    depth: int

    def tree_flatten(self):
        return (self.codes, self.order), (self.depth,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0])

    # -- queries ------------------------------------------------------------

    def node_keys(self, level: int) -> jnp.ndarray:
        """Per sorted point: its octree-node key at ``level``."""
        return morton.node_key(self.codes, level, self.depth)

    def node_range(self, key: jnp.ndarray, level: int):
        """[start, end) range in the sorted array of node ``key`` at
        ``level``.  Works for batched keys."""
        shift = jnp.uint32(3 * (self.depth - level))
        lo = (key.astype(jnp.uint32) << shift)
        hi = ((key.astype(jnp.uint32) + jnp.uint32(1)) << shift)
        start = jnp.searchsorted(self.codes, lo, side="left")
        end = jnp.searchsorted(self.codes, hi, side="left")
        return start, end

    def contains(self, query_codes: jnp.ndarray) -> jnp.ndarray:
        """Exact membership of full-depth codes (Overlap Detection hit
        test).  Returns bool mask, plus the index of the hit (or -1)."""
        pos = jnp.searchsorted(self.codes, query_codes, side="left")
        pos = jnp.clip(pos, 0, self.codes.shape[0] - 1)
        hit = self.codes[pos] == query_codes
        return hit, jnp.where(hit, pos, -1)


def build(points: jnp.ndarray, depth: int = morton.MAX_DEPTH,
          lo=None, hi=None, n_valid=None) -> LinearOctree:
    """Build the linear octree for a point cloud (N, 3).

    ``n_valid`` marks rows >= n_valid as padding: their codes become the
    uint32 sentinel (larger than any 30-bit Morton code), so the sorted
    order is *valid-first* — ``order[:n_valid]`` equals the order built
    on the unpadded prefix — and the quantization box is computed from
    valid rows only (arbitrary padding content cannot shift it).
    """
    if n_valid is not None and lo is None and hi is None:
        lo, hi = morton.masked_bounds(
            points, jnp.arange(points.shape[0]) < n_valid)
    codes = morton.morton_codes(points, depth, lo, hi)
    if n_valid is not None:
        codes = jnp.where(jnp.arange(points.shape[0]) < n_valid, codes,
                          jnp.uint32(morton.SENTINEL))
    order = jnp.argsort(codes)
    return LinearOctree(codes=codes[order], order=order.astype(jnp.int32),
                        depth=depth)


def prune(tree: LinearOctree, keep_sorted_idx: jnp.ndarray) -> LinearOctree:
    """The paper's Pruning Module: Sampled Octree = Input Octree restricted
    to the sampled (central) points.  ``keep_sorted_idx`` indexes the sorted
    arrays."""
    return LinearOctree(codes=tree.codes[keep_sorted_idx],
                        order=tree.order[keep_sorted_idx], depth=tree.depth)


@partial(jax.jit, static_argnames=("level", "depth"))
def adjacent_node_keys(keys: jnp.ndarray, level: int,
                       depth: int = morton.MAX_DEPTH) -> jnp.ndarray:
    """26-connectivity neighbor node keys (+ self) of octree nodes.

    keys: (...,) uint32 node keys at ``level``.  Returns (..., 27) uint32.
    Out-of-bounds neighbors are replaced by the node's own key (harmless
    duplicates for a neighbourhood lookup).
    """
    side = 1 << level
    # A node key at `level` is itself a Morton code over `level` bits/axis.
    xyz = morton.decode(keys.astype(jnp.uint32)).astype(jnp.int32)  # (...,3)
    offs = jnp.stack(jnp.meshgrid(jnp.arange(-1, 2), jnp.arange(-1, 2),
                                  jnp.arange(-1, 2), indexing="ij"),
                     axis=-1).reshape(27, 3)
    nxyz = xyz[..., None, :] + offs  # (..., 27, 3)
    valid = jnp.all((nxyz >= 0) & (nxyz < side), axis=-1)
    nxyz = jnp.clip(nxyz, 0, side - 1).astype(jnp.uint32)
    nkeys = morton.encode(nxyz)
    return jnp.where(valid, nkeys, keys[..., None].astype(jnp.uint32))
