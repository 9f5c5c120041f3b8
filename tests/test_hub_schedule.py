"""Hub-based Scheduling invariants + the octree-equivalence proof of the
overlap detection (paper §IV-B)."""
from functools import partial

import jax
import jax.numpy as jnp
from jax.extend.core import Literal
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # clean env: deterministic fallback sampler
    from _hyp import given, settings, strategies as st

from repro.core.islandize import Islands, islandize as _islandize
from repro.core import octree
from repro.core.hub_schedule import build_schedule
from repro.core.pipeline import LPCNConfig, data_structuring
from repro.data.synthetic import make_cloud
from test_islandize import _all_eqns


def _setup(n=256, s=128, k=16, seed=0, capacity=32):
    rng = np.random.default_rng(seed)
    xyz = jnp.asarray(make_cloud(rng, n))
    cfg = LPCNConfig(n_centers=s, k=k)
    cidx, nbr = data_structuring(cfg, xyz, jax.random.PRNGKey(seed))
    islands = _islandize(xyz[cidx], max(s // 32, 1), capacity=64,
                            key=jax.random.PRNGKey(seed))
    sched = build_schedule(islands, nbr, capacity)
    return xyz, cidx, nbr, islands, sched, capacity


def test_schedule_shapes_and_ranges():
    xyz, cidx, nbr, islands, sched, C = _setup()
    slot = np.asarray(sched.reuse_slot)
    assert slot.max() < C
    assert slot.min() >= -1
    pool = np.asarray(sched.pool_ids)
    assert pool.shape[1] == C


def _replay(members, nbr, C):
    """The FPGA's temporal Hub Cache replayed in numpy: each island's
    subsets in island-list order, each live point probed against the
    cache, inserted on a miss while capacity remains.  Padding rows and
    -1 neighbour slots neither probe nor fill.  -> (pool, reuse, first)
    as ``build_schedule`` lays them out."""
    H, M = members.shape
    K = nbr.shape[1]
    pool = np.full((H, C), -1, np.int32)
    reuse = np.full((H, M, K), -1, np.int32)
    first = np.zeros((H, M, K), bool)
    for h in range(H):
        cache: dict = {}
        for m, cidx_m in enumerate(members[h]):
            if cidx_m < 0:
                continue
            for kk, pid in enumerate(nbr[cidx_m]):
                if pid < 0:
                    continue
                if pid in cache:
                    reuse[h, m, kk] = cache[pid]
                    continue
                first[h, m, kk] = True
                if len(cache) < C:
                    cache[pid] = len(cache)
                    reuse[h, m, kk] = cache[pid]
                    pool[h, cache[pid]] = pid
                else:
                    cache[pid] = -1          # seen, but never cached
    return pool, reuse, first


def test_pool_is_first_occurrences_in_order():
    """Replay the island sequence in numpy (the FPGA temporal semantics)
    and check the closed-form schedule matches exactly."""
    xyz, cidx, nbr, islands, sched, C = _setup(seed=1)
    pool, reuse, first = _replay(np.asarray(islands.members),
                                 np.asarray(nbr), C)
    np.testing.assert_array_equal(np.asarray(sched.reuse_slot), reuse)
    np.testing.assert_array_equal(np.asarray(sched.pool_ids), pool)
    np.testing.assert_array_equal(np.asarray(sched.is_first), first)


#: (S, K, H, M, C, N) of every production schedule: pointnet2_c's two
#: blocks and dgcnn_c's EdgeConv layers
SHAPES = {"pn2c-1": (512, 32, 16, 64, 64, 1024),
          "pn2c-2": (128, 64, 4, 64, 128, 512),
          "dgcnn": (1024, 20, 32, 64, 40, 1024)}
CASES = ["full_rows", "padding_rows", "ragged", "small_capacity",
         "duplicates"]


def _oracle_inputs(rng, shape, case):
    """(members, nbr, C) of one cloud.  ``full_rows`` fills every row
    (subsets drawn with replacement); the others lay islands out as
    ``islandize`` does, a random count of members each with -1 rows
    after them (one island empty).  ``ragged`` also ends rows of
    neighbours in -1 slots, as ``n_valid`` padding does;
    ``small_capacity`` holds 8 entries, fewer than an island's distinct
    points; ``duplicates`` draws ids from a quarter of the cloud."""
    S, K, H, M, C, N = shape
    if case == "full_rows":
        members = rng.integers(0, S, (H, M))
    else:
        counts = rng.integers(1, M + 1, H)
        counts[rng.integers(H)] = 0
        members = np.full((H, M), -1)
        order = rng.permutation(S)
        taken = 0
        for h in range(H):
            c = min(counts[h], S - taken)
            members[h, :c] = order[taken:taken + c]
            taken += c
    nbr = rng.integers(0, N // 4 if case == "duplicates" else N, (S, K))
    if case == "ragged":
        nbr[np.arange(K) >= rng.integers(1, K + 1, (S, 1))] = -1
    if case == "small_capacity":
        C = 8
    return members.astype(np.int32), nbr.astype(np.int32), C


def _islands(members, S):
    """Islands of ``members`` (..., H, M) over S centers; ``solo`` and
    ``round_of`` are zeros, which ``build_schedule`` does not read."""
    members = jnp.asarray(members)
    lead = members.shape[:-2]
    return Islands(members, members[..., 0], jnp.zeros(lead + (S,), bool),
                   jnp.zeros(lead + (S,), jnp.int32))


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("shape", list(SHAPES), ids=list(SHAPES))
def test_schedule_matches_temporal_replay(shape, case, batched):
    """``build_schedule`` equals the temporal replay bit for bit at the
    production shapes, one cloud or a vmapped batch of two."""
    rng = np.random.default_rng(sorted(SHAPES).index(shape) * 10
                                + CASES.index(case))
    clouds = [_oracle_inputs(rng, SHAPES[shape], case)
              for _ in range(2 if batched else 1)]
    C = clouds[0][2]
    if batched:
        members = np.stack([c[0] for c in clouds])
        nbr = np.stack([c[1] for c in clouds])
        sched = jax.vmap(lambda i, n: build_schedule(i, n, C))(
            _islands(members, nbr.shape[-2]), jnp.asarray(nbr))
    else:
        members, nbr = clouds[0][0][None], clouds[0][1][None]
        sched = build_schedule(_islands(members[0], nbr.shape[-2]),
                               jnp.asarray(nbr[0]), C)
        sched = jax.tree.map(lambda a: a[None], sched)
    for b in range(len(clouds)):
        pool, reuse, first = _replay(members[b], nbr[b], C)
        np.testing.assert_array_equal(np.asarray(sched.pool_ids[b]), pool)
        np.testing.assert_array_equal(np.asarray(sched.reuse_slot[b]), reuse)
        np.testing.assert_array_equal(np.asarray(sched.is_first[b]), first)
        np.testing.assert_array_equal(np.asarray(sched.subset_valid[b]),
                                      members[b] >= 0)


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap"])
def test_schedule_sorts_scatters_and_gathers_nothing_by_data(batched):
    """The schedule is compares and reductions: no sort, no scatter, and
    one gather, the row gather of ``nbr_idx`` by the islands' members."""
    S, K, H, M, C, _ = SHAPES["pn2c-1"]
    lead = (4,) if batched else ()
    members = jnp.zeros(lead + (H, M), jnp.int32)
    fn = partial(build_schedule, cache_capacity=C)
    if batched:
        fn = jax.vmap(fn)
    closed = jax.make_jaxpr(fn)(_islands(members, S),
                                jnp.zeros(lead + (S, K), jnp.int32))
    (call,) = closed.jaxpr.eqns          # the jitted build_schedule
    inner = call.params["jaxpr"].jaxpr
    eqns = _all_eqns(inner)
    used = {e.primitive.name for e in eqns}
    assert not used & {"sort", "scatter", "scatter-add"}, used
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert len(gathers) == 1
    # the operand is build_schedule's nbr_idx, the indices come from
    # members (the first leaf of Islands) and never from nbr_idx
    members_in, nbr_in = inner.invars[0], inner.invars[-1]
    assert gathers[0].invars[0] is nbr_in
    made_from = {}
    for e in inner.eqns:
        src = set()
        for v in e.invars:
            if not isinstance(v, Literal):
                src |= made_from.get(v, {v})
        for v in e.outvars:
            made_from[v] = src
    roots = made_from[gathers[0].invars[1]]
    assert members_in in roots and nbr_in not in roots


def test_hub_fills_first_k_slots():
    xyz, cidx, nbr, islands, sched, C = _setup(seed=2)
    members = np.asarray(islands.members)
    nbr_np = np.asarray(nbr)
    pool = np.asarray(sched.pool_ids)
    for h in range(members.shape[0]):
        hub = members[h, 0]
        if hub < 0:
            continue
        hub_pts = []
        for pid in nbr_np[hub]:
            if pid not in hub_pts:
                hub_pts.append(pid)
        np.testing.assert_array_equal(pool[h, :len(hub_pts)], hub_pts)


def test_overlap_detection_octree_equivalence():
    """Membership-by-id == Morton-octree probe (the hardware mechanism):
    for each cached pool the octree built on pool points must report
    hit/miss identically to the id test."""
    xyz, cidx, nbr, islands, sched, C = _setup(seed=3)
    pool = np.asarray(sched.pool_ids)
    nbr_np = np.asarray(nbr)
    members = np.asarray(islands.members)
    # unique quantization for identity: include point index in the key
    # (two points may share a voxel; hardware stores per-point entries)
    for h in range(min(4, members.shape[0])):
        ids = pool[h][pool[h] >= 0]
        if len(ids) == 0:
            continue
        tree = octree.build(xyz[jnp.asarray(ids)])
        m = members[h, 1] if members.shape[1] > 1 else -1
        if m < 0:
            continue
        probe = xyz[jnp.asarray(nbr_np[m])]
        codes = octree.morton.morton_codes(
            probe, lo=xyz[jnp.asarray(ids)].min(0),
            hi=xyz[jnp.asarray(ids)].max(0))
        hit, _ = tree.contains(codes)
        id_hit = np.isin(nbr_np[m], ids)
        # octree hit must cover every id hit (same voxel => hit); spurious
        # voxel collisions are possible but rare
        assert (np.asarray(hit)[id_hit].mean() if id_hit.any() else 1.0) \
            > 0.9


@given(st.integers(0, 100), st.integers(8, 32))
@settings(max_examples=8, deadline=None)
def test_capacity_monotonicity(seed, cap):
    """More cache capacity never decreases reuse."""
    xyz, cidx, nbr, islands, _, _ = _setup(seed=seed)
    s1 = build_schedule(islands, nbr, cap)
    s2 = build_schedule(islands, nbr, cap * 2)
    r1 = int((np.asarray(s1.reuse_slot) >= 0).sum())
    r2 = int((np.asarray(s2.reuse_slot) >= 0).sum())
    assert r2 >= r1
