"""Plain reference of the L-PCN building block, shared by the families.

Written from the paper's description and the configuration alone; it
imports nothing of the program.  The geometric decisions (farthest point
sampling, exact kNN, the octree islands, the hub cache and the overflow
budget) are made on the host in numpy float32, one cloud at a time, with
loops where the algorithm is a loop.  The point MLPs run in jnp on the
default device, at float32 ``highest`` precision for the reference, or,
for a control, at emulated bfloat16x3 (``high``: three bf16 passes) or in
bfloat16 (``bf16``: every stored value rounded to bfloat16).

L-PCN's feature of a subset (center c, neighbour points p_k) is, per
output channel, the max over k of

* MLP([p_k - c, f_k])                          (computed exactly), or
* MLP([p_k - hub, f_k]) + (hub - c) . Wc       (read from the hub cache)

where Wc is the product of the coordinate rows of the first layer and the
later layers' matrices (linear compensation, paper Eq. 1).  A position is
read from the cache when its point is among the first C distinct points
of its island's sequence (hub subset first, then island-list order).
Positions that miss are computed exactly while the island's overflow
budget lasts; a subset with a miss beyond the budget, or one whose island
was full (solo), is computed exactly throughout.

Where float32 rounding decides, the reference reads each way.  Two
points whose distances to the sampled set tie within rounding may come
in either order out of farthest point sampling (a device may sum the
three squares in another order than numpy): ``fps_ways``.  A center
whose coordinate lies within rounding of a voxel boundary of the
islands' octree level has no one voxel (a chip's float32 division need
not round as numpy's does): ``boundary_ties``, and ``voxel_keys(flip=)``
places it on the other side.  ``rel_gap`` takes the nearest reading.
"""
from __future__ import annotations

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MAX_DEPTH = 10
SENTINEL = np.uint32(0xFFFFFFFF)
INT_MAX = np.iinfo(np.int32).max
BFS_ROUNDS = 32
#: how near (in finest-level octree cells, 1/1023 of the extent) to a
#: voxel boundary a center counts as on it: some 16 float32 ulps of the
#: quantized coordinate, several times what a division may be off by
BOUNDARY_TOL = 1e-3
#: most ties of one kind per block whose readings are tried
MAX_TIES = 6
#: how near (relative) the two largest distances of a farthest-point
#: step count as a tie: some 4 float32 ulps; a device may sum the three
#: squares in another order than numpy, which moves a distance by one
FPS_TOL = 5e-7


# ---- geometry (host, float32) ------------------------------------------------

def sq3(d: np.ndarray) -> np.ndarray:
    """Squared length over the last axis (3), in float32."""
    d = d.astype(np.float32, copy=False)
    return (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]) \
        + d[..., 2] * d[..., 2]


def fps(pts: np.ndarray, m: int, force=None):
    """Farthest point sampling from index 0 -> ((m,) int32, ties).  Once
    every point is taken, the first index of the largest distance
    repeats.  ``ties`` lists (step, runner-up) of each step whose two
    largest distances lie within ``FPS_TOL`` of each other, nearest
    first, at most ``MAX_TIES``; ``force`` = (step, index) takes that
    index at that step instead."""
    min_d = np.full(pts.shape[0], np.inf, np.float32)
    idx = np.zeros(m, np.int32)
    ties = []
    last = 0
    for i in range(1, m):
        min_d = np.minimum(min_d, sq3(pts - pts[last]))
        last = int(np.argmax(min_d))
        top = min_d[last]
        rest = min_d.copy()
        rest[last] = -np.inf
        second = int(np.argmax(rest))
        gap = (float(top) - float(rest[second])) / float(top) if top > 0 \
            else np.inf
        if gap <= FPS_TOL:
            ties.append((gap, i, second))
        if force is not None and force[0] == i:
            last = force[1]
        idx[i] = last
    return idx, [(i, j) for _, i, j in sorted(ties)[:MAX_TIES]]


def fps_ways(pts: np.ndarray, m: int) -> list[np.ndarray]:
    """The farthest point samplings a device's rounding may give: the
    plain one, then one per tie of it with the runner-up taken there."""
    idx, ties = fps(pts, m)
    return [idx] + [fps(pts, m, force=t)[0] for t in ties]


def knn(pts: np.ndarray, centers: np.ndarray, k: int) -> np.ndarray:
    """Exact k nearest points of each center, nearest first -> (S, k)."""
    d = sq3(centers[:, None, :] - pts[None, :, :])
    return np.argsort(d, axis=1, kind="stable")[:, :k].astype(np.int32)


def _part1by2(x):
    x = x.astype(np.uint32) & np.uint32(0x3FF)
    x = (x | (x << np.uint32(16))) & np.uint32(0x030000FF)
    x = (x | (x << np.uint32(8))) & np.uint32(0x0300F00F)
    x = (x | (x << np.uint32(4))) & np.uint32(0x030C30C3)
    x = (x | (x << np.uint32(2))) & np.uint32(0x09249249)
    return x


def _compact1by2(x):
    x = x.astype(np.uint32) & np.uint32(0x09249249)
    x = (x | (x >> np.uint32(2))) & np.uint32(0x030C30C3)
    x = (x | (x >> np.uint32(4))) & np.uint32(0x0300F00F)
    x = (x | (x >> np.uint32(8))) & np.uint32(0x030000FF)
    x = (x | (x >> np.uint32(16))) & np.uint32(0x3FF)
    return x


def morton_encode(iv):
    return (_part1by2(iv[..., 0]) | (_part1by2(iv[..., 1]) << np.uint32(1))
            | (_part1by2(iv[..., 2]) << np.uint32(2)))


def morton_decode(codes):
    return np.stack([_compact1by2(codes), _compact1by2(codes >> np.uint32(1)),
                     _compact1by2(codes >> np.uint32(2))], -1)


_OFFSETS = np.stack(np.meshgrid(np.arange(-1, 2), np.arange(-1, 2),
                                np.arange(-1, 2), indexing="ij"),
                    -1).reshape(27, 3)


def _scaled(centers: np.ndarray):
    """-> (lo (3,), extent, the centers in finest-level cells (S, 3))."""
    f32 = np.float32
    lo, hi = centers.min(0), centers.max(0)
    extent = f32(max(f32((hi - lo).max()), f32(1e-9)))
    n = (1 << MAX_DEPTH) - 1
    return lo, extent, (centers - lo) / extent * f32(n)


def boundary_ties(centers: np.ndarray, isl: dict) -> list[tuple[int, int]]:
    """(center, axis) of each coordinate within ``BOUNDARY_TOL`` of an
    inner voxel boundary at the islands' level, nearest first, at most
    ``MAX_TIES``."""
    level = isl["octree_level"]
    step = 1 << (MAX_DEPTH - level)
    scaled = _scaled(centers)[2].astype(np.float64)
    m = np.round(scaled / step)
    off = np.abs(scaled - m * step)
    near = (off < BOUNDARY_TOL) & (m > 0) & (m < (1 << level))
    ties = sorted(zip(off[near], *np.nonzero(near)))
    return [(int(c), int(a)) for _, c, a in ties[:MAX_TIES]]


def tie_flips(ties: list) -> list[tuple]:
    """Every combination of the ties to place across their boundary, the
    empty one (no flip) first."""
    return [f for r in range(len(ties) + 1)
            for f in itertools.combinations(ties, r)]


def voxel_keys(centers: np.ndarray, isl: dict, flip=()) -> np.ndarray:
    """Morton key (S,) of each center's voxel of the sampled octree at the
    islands' level, in the centers' bounding box.  ``flip`` holds
    (center, axis) pairs of ``boundary_ties`` to place in the voxel
    across the boundary from where the rounding put them."""
    level = isl["octree_level"]
    n = (1 << MAX_DEPTH) - 1
    iv = np.clip(_scaled(centers)[2], 0, n).astype(np.uint32)
    step = 1 << (MAX_DEPTH - level)
    for c, a in flip:
        edge = int(round(int(iv[c, a]) / step)) * step
        iv[c, a] = edge - 1 if iv[c, a] >= edge else edge
    return morton_encode(iv) >> np.uint32(3 * (MAX_DEPTH - level))


def islands(centers: np.ndarray, hub_scores: np.ndarray, isl: dict,
            flip=()):
    """Octree islandization of one cloud's centers (S, 3), with the
    boundary ties of ``flip`` placed across (``voxel_keys``).

    Returns (members (H, M) int32 with -1 padding, solo (S,) bool, hub
    (H,) int32 center indices)."""
    s = centers.shape[0]
    h = max(s // isl["subsets_per_island"], 1)
    cap = isl["capacity"]
    level = isl["octree_level"]
    f32 = np.float32
    lo, extent, _ = _scaled(centers)
    ckeys = voxel_keys(centers, isl, flip)
    uniq = np.unique(ckeys)
    ukeys = np.full(s, SENTINEL, np.uint32)
    ukeys[:uniq.size] = uniq
    nvox = uniq.size
    vox_of = np.searchsorted(ukeys, ckeys).astype(np.int32)
    side = 1 << level
    vxyz = morton_decode(uniq).astype(f32)
    vcenter = lo + (vxyz + f32(0.5)) / f32(side) * extent
    # 26-neighbourhood (+ self) of every occupied voxel, -1 where empty
    ixyz = morton_decode(uniq).astype(np.int64)[:, None, :] + _OFFSETS
    inside = np.all((ixyz >= 0) & (ixyz < side), -1)
    nkeys = morton_encode(np.clip(ixyz, 0, side - 1).astype(np.uint32))
    nkeys = np.where(inside, nkeys, uniq[:, None])
    pos = np.clip(np.searchsorted(uniq, nkeys), 0, nvox - 1)
    nbr = np.where(uniq[pos] == nkeys, pos, -1)               # (V, 27)

    # hubs: the h centers of lowest score; a voxel holding two hubs
    # belongs to the later one
    hub = np.argsort(hub_scores, kind="stable")[:h].astype(np.int32)
    hub_xyz = centers[hub]
    assign = np.full(nvox, -1, np.int32)
    for j in range(h):
        assign[vox_of[hub[j]]] = j
    rnd = np.where(assign >= 0, 0, INT_MAX).astype(np.int64)
    # rounds of breadth-first gathering: an unassigned voxel joins the hub
    # of the nearest (to its center) already-gathered neighbour voxel
    for r in range(1, BFS_ROUNDS + 1):
        safe = np.clip(nbr, 0, None)
        nass = np.where(nbr >= 0, assign[safe], -1)
        nrnd = np.where(nbr >= 0, rnd[safe], INT_MAX)
        front = (nass >= 0) & (nrnd < r)
        if not front.any():
            break
        d = sq3(hub_xyz[np.clip(nass, 0, h - 1)] - vcenter[:, None, :])
        d = np.where(front, d, np.inf)
        best = np.argmin(d, -1)
        reach = np.isfinite(d.min(-1)) & (assign < 0)
        new = nass[np.arange(nvox), best]
        assign = np.where(reach, new, assign)
        rnd = np.where(reach, r, rnd)
    left = assign < 0                       # not connected: nearest hub
    if left.any():
        d_all = sq3(vcenter[:, None, :] - hub_xyz[None, :, :])
        assign = np.where(left, np.argmin(d_all, -1), assign)
        rnd = np.where(left, BFS_ROUNDS + 1, rnd)
    isl_of = assign[vox_of]
    round_of = rnd[vox_of].astype(f32)
    d_hub = sq3(centers - hub_xyz[isl_of])
    is_hub = np.zeros(s, bool)
    is_hub[hub] = True
    # island lists: hub first, then by gathering round, then distance
    order = np.lexsort((d_hub, round_of, (~is_hub).astype(np.int32), isl_of))
    members = np.full((h, cap), -1, np.int32)
    solo = np.zeros(s, bool)
    fill = np.zeros(h, np.int64)
    for c in order:
        i = isl_of[c]
        if fill[i] < cap:
            members[i, fill[i]] = c
        else:
            solo[c] = True
        fill[i] += 1
    return members, solo, hub


def reuse_plan(members, solo, hub, nbr: np.ndarray, k: int, isl: dict):
    """The hub-cache plan of one block.

    -> (cached (S, K) bool: read from the hub cache, hub_of (S,) int32:
    the center whose position the cached values are relative to)."""
    s = nbr.shape[0]
    h, cap = members.shape
    c_slots = int(isl["cache_x"] * k)
    budget = max(int(isl["overflow_frac"] * cap * k), k)
    cached = np.zeros((s, k), bool)
    exact_row = solo.copy()
    hub_of = np.arange(s, dtype=np.int32)
    for i in range(h):
        rows = members[i][members[i] >= 0]
        if rows.size == 0:
            continue
        seq = nbr[rows].reshape(-1)
        # the hub cache holds the first c_slots distinct points
        _, first = np.unique(seq, return_index=True)
        kept = seq[np.sort(first)[:c_slots]]
        hit = np.isin(seq, kept).reshape(rows.size, k)
        # misses are computed while the island's overflow budget lasts
        miss_rows = np.nonzero(~hit)[0]
        over = np.unique(miss_rows[budget:])
        exact_row[rows[over]] = True
        cached[rows] = hit
        hub_of[rows] = hub[i]
    cached[exact_row] = False
    return cached, hub_of


def rel_gap(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per answer: the largest |logit difference| over the reference's
    largest |logit|, against the nearest of the reference's readings of
    that answer.  got (..., classes), ref (..., readings, classes)."""
    g = np.abs(got[..., None, :] - ref).max(-1) / np.abs(ref).max(-1)
    return g.min(-1)


# ---- point MLPs (device) -----------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST


def _to_bf16(a):
    """``a`` rounded to bfloat16, kept in float32.  ``reduce_precision``
    and not a pair of casts: XLA may drop a cast pair, since it allows
    excess precision by default; with casts, the ``high`` control read
    on the TPU like one bf16 pass, ~6e-3 against ~1e-5 on the CPU."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _bf16_parts(a):
    hi = _to_bf16(a)
    return hi, _to_bf16(a - hi)


def store(a, precision: str):
    """A value as it is stored at ``precision`` (bf16: rounded)."""
    return _to_bf16(a) if precision == "bf16" else a


def matmul(x, w, precision: str):
    """x @ w at float32 ``highest``; as three bf16 passes (``high``:
    hi*hi + hi*lo + lo*hi, products exact, f32 accumulation); or on
    bf16 operands with the product rounded to bf16 (``bf16``)."""
    mm = partial(jnp.matmul, precision=HIGHEST)
    if precision == "highest":
        return mm(x, w)
    if precision == "bf16":
        return store(mm(store(x, precision), store(w, precision)), precision)
    if precision != "high":
        raise ValueError(f"unknown reference precision {precision!r}")
    xh, xl = _bf16_parts(x)
    wh, wl = _bf16_parts(w)
    return mm(xh, wh) + (mm(xh, wl) + mm(xl, wh))


def mlp(layers, x, activation: str, precision: str):
    """Point MLP; ReLU between layers when ``activation == 'per_layer'``,
    none inside a ``block_end`` MLP."""
    n = len(layers)
    for i, (w, b) in enumerate(layers):
        x = store(matmul(x, w, precision) + store(b, precision), precision)
        if activation == "per_layer" and i < n - 1:
            x = jax.nn.relu(x)
    return x


def comp_matrix(layers, d: int, precision: str, kind: str = "sa"):
    """Wc: the linear action of a center shift on the MLP's output."""
    w0 = layers[0][0]
    m = w0[:d] if kind == "sa" else w0[:d] - w0[d:2 * d]
    for w, _ in layers[1:]:
        m = matmul(m, w, precision)
    return m


def gather(a, idx):
    """Per-cloud gather: a (C, N, ...) at idx (C, ...) -> (C, ..., ...)."""
    return jax.vmap(lambda x, i: x[i])(a, idx)
