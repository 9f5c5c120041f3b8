"""Islandization invariants (paper §IV-A), incl. hypothesis properties."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # clean env: deterministic fallback sampler
    from _hyp import given, settings, strategies as st

from repro.core.islandize import islandize as _islandize
from repro.data.synthetic import make_cloud


def _centers(n, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(make_cloud(rng, max(n, 16))[:n])


@pytest.mark.parametrize("n_hubs", [2, 4, 8])
def test_partition_property(n_hubs):
    """Every center is in exactly one island OR solo (paper: 'a point
    subset cannot belong to more than one island')."""
    centers = _centers(128)
    out = _islandize(centers, n_hubs, capacity=64,
                        key=jax.random.PRNGKey(0))
    members = np.asarray(out.members)
    solo = np.asarray(out.solo)
    flat = members[members >= 0]
    assert len(set(flat.tolist())) == len(flat)      # no duplicates
    covered = set(flat.tolist()) | set(np.where(solo)[0].tolist())
    assert covered == set(range(128))                # complete


def test_hub_first_and_round_order():
    centers = _centers(128, seed=1)
    out = _islandize(centers, 4, capacity=64,
                        key=jax.random.PRNGKey(1))
    members = np.asarray(out.members)
    rounds = np.asarray(out.round_of)
    hubs = set(np.asarray(out.hub).tolist())
    for h in range(4):
        row = members[h][members[h] >= 0]
        if len(row) == 0:
            continue
        assert row[0] in hubs                        # hub at slot 0
        r = rounds[row]
        assert (np.diff(r) >= 0).all()               # inside-to-outside


def test_islands_spatially_coherent():
    """Mean intra-island distance < mean cross-island distance."""
    centers = _centers(256, seed=2)
    out = _islandize(centers, 8, capacity=64,
                        key=jax.random.PRNGKey(2))
    members = np.asarray(out.members)
    c = np.asarray(centers)
    intra, cross = [], []
    means = []
    for h in range(8):
        row = members[h][members[h] >= 0]
        if len(row) < 2:
            continue
        pts = c[row]
        means.append(pts.mean(0))
        intra.append(np.linalg.norm(pts - pts.mean(0), axis=1).mean())
    means = np.array(means)
    if len(means) > 1:
        cross = np.linalg.norm(means[:, None] - means[None, :],
                               axis=-1)
        cross = cross[cross > 0].mean()
        assert np.mean(intra) < cross


@given(st.integers(1, 6), st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_partition_property_fuzz(n_hubs, seed):
    centers = _centers(64, seed=seed)
    out = _islandize(centers, n_hubs, capacity=32,
                        key=jax.random.PRNGKey(seed))
    members = np.asarray(out.members)
    solo = np.asarray(out.solo)
    flat = members[members >= 0]
    assert len(set(flat.tolist())) == len(flat)
    assert set(flat.tolist()) | set(np.where(solo)[0].tolist()) \
        == set(range(64))


def test_fps_hub_selection_reduces_solo():
    """FPS hub selection (beyond-paper option) preserves the partition
    property.  NOTE: measured across seeds FPS is NOT consistently better
    than the paper's random hubs — FPS picks boundary points, growing
    islands unevenly (hypothesis refuted; EXPERIMENTS.md §Perf notes)."""
    for seed in (3, 4):
        centers = _centers(256, seed=seed)
        out = _islandize(centers, 8, capacity=48, hub_select="fps",
                         key=jax.random.PRNGKey(seed))
        members = np.asarray(out.members)
        flat = members[members >= 0]
        assert len(set(flat.tolist())) == len(flat)
        covered = set(flat.tolist()) | set(
            np.where(np.asarray(out.solo))[0].tolist())
        assert covered == set(range(256))


# ---- oracle: a plain numpy gather-BFS over the occupied voxels --------------

_OFFS = np.stack(np.meshgrid(*[np.arange(-1, 2)] * 3, indexing="ij"),
                 -1).reshape(27, 3)
_INT_MAX = np.iinfo(np.int32).max


def _sq3(d):
    return (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]) \
        + d[..., 2] * d[..., 2]


def _oracle(centers, hubs, *, level, capacity, max_rounds,
            valid=None, n_hubs_valid=None):
    """Islandization of (S, 3) float32 ``centers`` around the center indices
    ``hubs``: voxelize at ``level``, grow every hub's island by rounds over
    the 26-neighbourhood (each unreached voxel takes the hub nearest its
    center among gathered neighbours, first of equal slots), send what no
    round reaches to the nearest hub, then list islands hub first, by
    round, then by distance.  -> (members, solo, round_of)."""
    c = np.asarray(centers, np.float32)
    s, h = c.shape[0], len(hubs)
    valid = np.ones(s, bool) if valid is None else np.asarray(valid)
    hub_ok = np.arange(h) < (h if n_hubs_valid is None else int(n_hubs_valid))
    f32 = np.float32
    lo, hi = c[valid].min(0), c[valid].max(0)
    extent = f32(max(f32((hi - lo).max()), f32(1e-9)))
    fine = np.clip((c - lo) / extent * f32(1023), 0, 1023).astype(np.uint32)
    ivox = (fine >> np.uint32(10 - level)).astype(np.int64)
    uniq, vox_of = np.unique(ivox[valid], axis=0, return_inverse=True)
    vox = np.full(s, -1)
    vox[valid] = vox_of.reshape(-1)
    side = 1 << level
    vcenter = lo + (uniq.astype(f32) + f32(0.5)) / f32(side) * extent
    index = {tuple(v): i for i, v in enumerate(uniq.tolist())}
    nbr = np.array([[index.get(tuple(v + o), -1) for o in _OFFS.tolist()]
                    for v in uniq])                                  # (V, 27)

    hub_xyz = c[hubs]
    assign = np.full(len(uniq), -1)
    for j in np.flatnonzero(hub_ok):          # a later hub wins its voxel
        assign[vox[hubs[j]]] = j
    rnd = np.where(assign >= 0, 0, _INT_MAX)
    for r in range(1, max_rounds + 1):
        nass = np.where(nbr >= 0, assign[nbr], -1)
        nrnd = np.where(nbr >= 0, rnd[nbr], _INT_MAX)
        d = _sq3(hub_xyz[np.clip(nass, 0, h - 1)] - vcenter[:, None, :])
        d = np.where((nass >= 0) & (nrnd < r), d, np.inf)
        reach = np.isfinite(d.min(-1)) & (assign < 0)
        assign = np.where(reach, nass[np.arange(len(uniq)), d.argmin(-1)],
                          assign)
        rnd = np.where(reach, r, rnd)
    left = assign < 0
    d_all = np.where(hub_ok, _sq3(vcenter[:, None] - hub_xyz[None]), np.inf)
    assign = np.where(left, d_all.argmin(-1), assign)
    rnd = np.where(left, max_rounds + 1, rnd)

    isl = np.where(valid, assign[vox], h)
    round_of = np.where(valid, rnd[vox], _INT_MAX)
    d_hub = _sq3(c - hub_xyz[np.clip(isl, 0, h - 1)])
    is_hub = np.zeros(s, bool)
    is_hub[hubs[hub_ok]] = True
    order = np.lexsort((d_hub, round_of.astype(f32), ~is_hub, isl))
    members = np.full((h, capacity), -1, np.int32)
    solo = np.zeros(s, bool)
    fill = np.zeros(h + 1, int)
    for i in order:
        if isl[i] < h and fill[isl[i]] < capacity:
            members[isl[i], fill[isl[i]]] = i
        elif isl[i] < h:
            solo[i] = True
        fill[isl[i]] += 1
    return members, solo, round_of


def _oracle_hubs(centers, n_hubs, hub_select, key, valid=None):
    from repro.core.sampling import farthest_point_sampling, index_uniform
    if hub_select == "fps":
        return np.asarray(farthest_point_sampling(
            jnp.asarray(centers), n_hubs, valid=valid))
    scores = np.asarray(index_uniform(key, centers.shape[0]))
    if valid is not None:
        scores = np.where(valid, scores, np.inf)
    return np.argsort(scores, kind="stable")[:n_hubs].astype(np.int32)


def _two_clusters(s, seed):
    """A cloud and a small far-away cluster: some voxels no round reaches."""
    rng = np.random.default_rng(seed)
    far = max(s // 32, 2)
    return np.concatenate([
        rng.normal(0.0, 0.05, (s - far, 3)),
        rng.normal(0.0, 0.01, (far, 3)) + 1.0]).astype(np.float32)


def _lattice():
    """An 8 x 8 x 2 integer lattice: voxels equidistant from two hubs, so
    ties between neighbour slots decide islands."""
    return np.stack(np.meshgrid(np.arange(8), np.arange(8), np.arange(2),
                                indexing="ij"), -1).reshape(-1, 3)


_ORACLE_CASES = (
    [pytest.param(s, level, sel, "plain", id=f"S{s}-L{level}-{sel}")
     for s in (64, 128, 512) for level in (2, 3, 4, 5)
     for sel in ("random", "fps")]
    + [pytest.param(128, 4, "random", "padded", id="padded"),
       pytest.param(128, 4, "random", "clusters", id="clusters"),
       pytest.param(128, 4, "random", "vmap", id="vmap-B4"),
       pytest.param(128, 3, "random", "lattice", id="lattice-ties")])


@pytest.mark.parametrize("s,level,hub_select,case", _ORACLE_CASES)
def test_islandize_matches_gather_bfs_oracle(s, level, hub_select, case):
    """Bit for bit against the numpy gather-BFS: members, hub, solo and
    round_of, for plain, padded (ragged contract), disconnected, vmapped
    (as ``structure_block`` calls it) and tied clouds."""
    n_hubs = s // 16 if case == "lattice" else max(s // 32, 1)
    cap, rounds = 32, 32
    b = 4 if case == "vmap" else 1
    make = {"clusters": _two_clusters,
            "lattice": lambda s, seed: _lattice()}.get(
        case, lambda s, seed: np.asarray(_centers(s, seed=seed)))
    clouds = np.stack([make(s, seed) for seed in range(b)]).astype(np.float32)
    keys = jax.random.split(jax.random.PRNGKey(s + level), b)
    n_valid = (np.array([s, s - 7, s // 2 + 3, s - 40])[:b]
               if case in ("padded", "vmap") else np.full(b, s))
    valid = np.arange(s)[None, :] < n_valid[:, None]
    nh_valid = np.maximum(n_valid // 32, 1)
    ragged = case in ("padded", "vmap")

    def run(c, k, v, nh):
        return _islandize(c, n_hubs, level=level, capacity=cap,
                          hub_select=hub_select, max_rounds=rounds, key=k,
                          center_valid=v if ragged else None,
                          n_hubs_valid=nh if ragged else None)

    out = jax.vmap(run)(jnp.asarray(clouds), keys, jnp.asarray(valid),
                        jnp.asarray(nh_valid))
    for i in range(b):
        v = valid[i] if ragged else None
        hubs = _oracle_hubs(clouds[i], n_hubs, hub_select, keys[i], v)
        members, solo, round_of = _oracle(
            clouds[i], hubs, level=level, capacity=cap, max_rounds=rounds,
            valid=v, n_hubs_valid=nh_valid[i] if ragged else None)
        np.testing.assert_array_equal(np.asarray(out.hub[i]), hubs)
        np.testing.assert_array_equal(np.asarray(out.members[i]), members)
        np.testing.assert_array_equal(np.asarray(out.solo[i]), solo)
        np.testing.assert_array_equal(np.asarray(out.round_of[i]), round_of)
        if case == "clusters":          # the nearest-hub fallback ran
            assert (round_of == rounds + 1).any()


def _subjaxprs(eqn):
    """The jaxprs an equation holds (loop bodies, branches, calls)."""
    subs = []
    for p in eqn.params.values():
        for q in p if isinstance(p, (tuple, list)) else (p,):
            if hasattr(q, "eqns") or hasattr(q, "jaxpr"):
                subs.append(getattr(q, "jaxpr", q))
    return subs


def _all_eqns(jaxpr):
    """Every equation of ``jaxpr``, nested ones included."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for sub in _subjaxprs(eqn):
            out += _all_eqns(sub)
    return out


@pytest.mark.parametrize("batched", [False, True], ids=["single", "vmap"])
def test_bfs_loop_indexes_nothing_by_data(batched):
    """The BFS rounds (the one loop of ``max_rounds`` trips) read their
    neighbours by static shifts: no gather, scatter or dynamic slice in
    its body."""
    rounds = 29                 # a trip count no other loop here has
    fn = partial(_islandize, n_hubs=4, level=4, capacity=32,
                 max_rounds=rounds)
    if batched:
        fn = jax.vmap(fn)
    centers = jnp.zeros((4, 512, 3) if batched else (512, 3))
    bfs = [e for e in _all_eqns(jax.make_jaxpr(fn)(centers).jaxpr)
           if e.primitive.name == "scan" and e.params["length"] == rounds]
    assert len(bfs) == 1
    used = {e.primitive.name for sub in _subjaxprs(bfs[0])
            for e in _all_eqns(sub)}
    banned = {"gather", "scatter", "scatter-add", "dynamic_slice",
              "dynamic_update_slice"}
    assert not used & banned, used & banned
