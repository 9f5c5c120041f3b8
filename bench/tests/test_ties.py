"""Where rounding decides the geometry (a tie in farthest point sampling,
a center on a voxel boundary of the islands) the reference has a reading
for each way, and an answer is judged against the nearest."""
import numpy as np

from bench import refcore as rc

ISL = {"octree_level": 4}


def _centers_with_one_on_a_boundary():
    rng = np.random.default_rng(4)
    centers = rng.uniform(-1, 1, (64, 3)).astype(np.float32)
    centers[:, 0] = np.clip(centers[:, 0], -0.9, 0.9)
    centers[0, 0], centers[1, 0] = -1.0, 1.0        # the box in x: [-1, 1]
    # cell 512 of 1023 lies 1024/1023 * 0.5 - 1 along x; a voxel of the
    # level-4 octree spans 64 cells, so 512 is a boundary (8 * 64)
    centers[5, 0] = np.float32(-1.0 + 2.0 * 512 / 1023)
    return centers


def test_boundary_tie_found_and_flipped():
    centers = _centers_with_one_on_a_boundary()
    assert rc.boundary_ties(centers, ISL) == [(5, 0)]
    plain = rc.voxel_keys(centers, ISL)
    flipped = rc.voxel_keys(centers, ISL, flip=[(5, 0)])
    assert np.nonzero(plain != flipped)[0].tolist() == [5]
    # across the boundary: the x bits of the two keys differ, y and z not
    x = lambda k: rc.morton_decode(k)[..., 0]  # noqa: E731
    assert abs(int(x(plain[5])) - int(x(flipped[5]))) == 1


def test_tie_flips_every_combination_plain_first():
    flips = rc.tie_flips([(1, 0), (2, 2)])
    assert flips == [(), ((1, 0),), ((2, 2),), ((1, 0), (2, 2))]
    assert rc.tie_flips([]) == [()]


def test_rel_gap_takes_the_nearer_reading():
    r0 = np.array([1.0, -2.0, 4.0])
    r1 = np.array([1.0, -2.0, 4.4])
    ref = np.stack([np.stack([r0, r1]), np.stack([r0, r0])])  # (2, 2, 3)
    got = np.stack([r1, r1])
    gap = rc.rel_gap(got, ref)
    assert gap[0] == 0.0                       # the second reading
    assert np.isclose(gap[1], 0.4 / 4.0)       # the only reading
    assert np.isnan(rc.rel_gap(np.array([np.nan, 0, 0]), ref[0]))


def test_fps_tie_gives_the_other_order():
    pts = np.array([[0, 0, 0], [1, 0, 0], [0, 1, 0], [0.1, 0.1, 0]],
                   np.float32)
    idx, ties = rc.fps(pts, 3)
    assert idx.tolist() == [0, 1, 2] and ties == [(1, 2)]
    ways = rc.fps_ways(pts, 3)
    assert [w.tolist() for w in ways] == [[0, 1, 2], [0, 2, 1]]


def test_fps_without_ties_has_one_way():
    pts = np.array([[0, 0, 0], [1, 0, 0], [0, 0.9, 0], [0.1, 0.1, 0]],
                   np.float32)
    assert [w.tolist() for w in rc.fps_ways(pts, 3)] == [[0, 1, 2]]
