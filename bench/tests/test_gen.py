"""Seeded generators: reproducible, and the same load for every seed."""
import numpy as np

from bench import gen

BIG_SEED = 2**31 + 12345


def test_clouds_and_keys_repeat_for_a_seed():
    a = gen.make_clouds(gen.rng_for(BIG_SEED, 2), [1024, 300])
    b = gen.make_clouds(gen.rng_for(BIG_SEED, 2), [1024, 300])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
        assert x.dtype == np.float32 and np.abs(x).max() <= 1.0 + 1e-6
    np.testing.assert_array_equal(gen.jax_key_words(BIG_SEED, 3, n=4),
                                  gen.jax_key_words(BIG_SEED, 3, n=4))
    assert not np.array_equal(gen.jax_key_words(BIG_SEED, 3, n=4),
                              gen.jax_key_words(BIG_SEED + 1, 3, n=4))


def test_open_loop_offers_the_same_load_to_every_seed():
    kw = dict(rate_hz=40.0, seconds=20.0, size_median=768, size_sigma=0.35,
              size_min=128, size_max=1024)
    d1, s1 = gen.open_loop_schedule(1, **kw)
    d2, s2 = gen.open_loop_schedule(BIG_SEED, **kw)
    assert len(s1) == len(s2) == 800
    assert sorted(s1) == sorted(s2)
    assert not np.array_equal(s1, s2)
    # the gaps are one multiset, less the one before the first request
    g1, g2 = set(np.round(np.diff(d1), 12)), set(np.round(np.diff(d2), 12))
    assert len(g1 ^ g2) <= 2
    assert d1[0] == 0 and 18.0 < d1[-1] < 20.0
    assert np.median(s1) == 768 and s1.min() >= 128 and s1.max() <= 1024


def test_fixed_sizes_give_every_request_the_same_cloud_size():
    d, s = gen.open_loop_schedule(BIG_SEED, rate_hz=32.0, seconds=51.0,
                                  size_median=1024, size_sigma=0.0,
                                  size_min=1024, size_max=1024)
    assert len(s) == 1632 and set(s) == {1024}
    assert d[0] == 0 and 48.0 < d[-1] < 51.0


def test_quantile_counts_missing_answers_as_infinite():
    assert gen.quantile([1.0, 2.0, 3.0, np.inf], 0.5) == 2.0
    assert gen.quantile([1.0] * 19 + [np.inf], 0.95) == 1.0
    assert gen.quantile([1.0] * 18 + [np.inf] * 2, 0.95) == np.inf
