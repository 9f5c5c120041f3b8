"""Seeded input generators: object clouds, request sizes and arrivals.

The cloud generator is a copy of the program's ``make_cloud``
(``repro.data.synthetic``): surface samples of 3-6 composited primitives
(spheres, boxes, cylinders), normalised to the unit ball, as a stand-in
for ModelNet40 objects.  It is copied so that a change to the program
cannot move the benchmark's inputs.

Sizes and arrivals of the open loop follow the program's
``synthetic_trace`` (log-normal sizes, Poisson arrivals), with one
change for steadiness: every seed draws the same multiset of sizes and
inter-arrival gaps (their quantiles) and only permutes them, so two
seeds offer the same work in a different order.
"""
from __future__ import annotations

import math

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of ``seed`` (any size of
    non-negative integer)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def jax_key_words(seed: int, *stream: int, n: int = 1) -> np.ndarray:
    """``n`` raw uint32 PRNG key pairs (n, 2) drawn from ``seed``."""
    ss = np.random.SeedSequence([int(seed), *stream])
    return ss.generate_state(2 * n, np.uint32).reshape(n, 2)


def _sphere(rng, n, c, r):
    v = rng.normal(size=(n, 3))
    v /= np.linalg.norm(v, axis=1, keepdims=True) + 1e-9
    return c + r * v


def _box(rng, n, c, s):
    face = rng.integers(0, 6, n)
    u = rng.uniform(-0.5, 0.5, (n, 3))
    axis, side = face % 3, (face // 3) * 1.0 - 0.5
    u[np.arange(n), axis] = side
    return c + u * s


def _cylinder(rng, n, c, r, h):
    th = rng.uniform(0, 2 * np.pi, n)
    z = rng.uniform(-h / 2, h / 2, n)
    return c + np.stack([r * np.cos(th), r * np.sin(th), z], -1)


def make_cloud(rng: np.random.Generator, n_points: int) -> np.ndarray:
    """One synthetic object cloud (n_points, 3) float32 in the unit ball."""
    prims = []
    n_parts = rng.integers(3, 7)
    share = rng.dirichlet(np.ones(n_parts) * 2.0) * n_points
    share = np.maximum(share.astype(int), 8)
    for ns in share:
        c = rng.uniform(-0.6, 0.6, 3)
        kind = rng.integers(0, 3)
        if kind == 0:
            prims.append(_sphere(rng, ns, c, rng.uniform(0.1, 0.4)))
        elif kind == 1:
            prims.append(_box(rng, ns, c, rng.uniform(0.1, 0.5, 3)))
        else:
            prims.append(_cylinder(rng, ns, c, rng.uniform(0.05, 0.3),
                                   rng.uniform(0.2, 0.8)))
    pts = np.concatenate(prims)[:n_points]
    if pts.shape[0] < n_points:  # pad by resampling
        extra = pts[rng.integers(0, pts.shape[0], n_points - pts.shape[0])]
        pts = np.concatenate([pts, extra])
    pts += 0.005 * rng.normal(size=pts.shape)  # sensor noise
    pts -= pts.mean(0)
    pts /= np.abs(pts).max() + 1e-9
    return pts.astype(np.float32)


def make_clouds(rng: np.random.Generator, sizes) -> list[np.ndarray]:
    return [make_cloud(rng, int(n)) for n in sizes]


def _normal_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9)."""
    a = (-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00)
    b = (-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01)
    c = (-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549671010284580e+00,
         4.374664141464968e+00, 2.938163982698783e+00)
    d = (7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00)
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
               + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q
                           + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                 + a[5]) * q) / (((((b[0] * r + b[1]) * r + b[2]) * r
                                   + b[3]) * r + b[4]) * r + 1)
    return out


def open_loop_schedule(seed: int, *, rate_hz: float, seconds: float,
                       size_median: float, size_sigma: float,
                       size_min: int, size_max: int):
    """-> (due offsets (n,) s, sizes (n,) int) for one open-loop run.

    n = round(rate_hz * seconds).  Gaps are the n mid-quantiles of an
    exponential of mean 1/rate_hz and sizes the n mid-quantiles of the
    log-normal (median ``size_median``, log-std ``size_sigma``), clipped
    to [size_min, size_max]; ``seed`` permutes both, so every seed offers
    the same load."""
    n = max(int(round(rate_hz * seconds)), 1)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate_hz
    sizes = np.round(size_median * np.exp(size_sigma * _normal_ppf(u)))
    sizes = np.clip(sizes, size_min, size_max).astype(int)
    rng = rng_for(seed, 7)
    gaps = gaps[rng.permutation(n)]
    sizes = sizes[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]           # first request due at 0
    return due, sizes


def quantile(values, q: float) -> float:
    """The q-quantile (0..1) of ``values`` by the nearest-rank rule;
    ``inf`` entries (missing answers) sort last."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    return float(v[min(int(math.ceil(q * v.size)) - 1, v.size - 1)])
