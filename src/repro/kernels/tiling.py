"""Shared tile/lane helpers for the batched FC kernels.

TPU tiles are (8, 128) for f32: the MXU/VPU want the minor (lane) axis in
multiples of 128 and the second-minor (sublane) axis in multiples of 8.
The FC kernels pad their contraction/output lanes up front (zero lanes
through a matmul are exact no-ops) and slice the output back, so Mosaic
never sees a ragged lane dimension; tile sizes along the grid axes are
derived from a VMEM budget instead of being hardcoded.
"""
from __future__ import annotations

import jax.numpy as jnp

LANE = 128          # f32 minor-axis tile
SUBLANE = 8         # f32 second-minor-axis tile
F32_BYTES = 4
DEFAULT_VMEM_BUDGET_MB = 8.0   # of ~16 MB/core; leaves double-buffer room


def round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def pad_axis(x: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    """Zero-pad ``axis`` of ``x`` up to length ``target`` (no-op if equal)."""
    cur = x.shape[axis]
    if cur == target:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - cur)
    return jnp.pad(x, widths)


def pad_lanes(x: jnp.ndarray, multiple: int = LANE) -> jnp.ndarray:
    """Zero-pad the last (lane) axis of ``x`` to a multiple of ``multiple``."""
    return pad_axis(x, x.ndim - 1, round_up(x.shape[-1], multiple))


def block_bytes(block_shape, dtype=jnp.float32) -> int:
    """Bytes of one VMEM block buffer (non-int dims — e.g. vmap-mapped
    entries — count as 1)."""
    n = 1
    for d in block_shape:
        n *= d if isinstance(d, int) else 1
    return n * jnp.dtype(dtype).itemsize


def call_footprint_bytes(streamed_bytes: int, resident_bytes: int) -> int:
    """Jaxpr-visible VMEM footprint of one pallas_call grid step: streamed
    blocks are double-buffered, resident (constant-index-map) blocks are
    not.  This is the byte model ``repro.analysis`` lints against."""
    return 2 * streamed_bytes + resident_bytes


def mlp_weight_elems(dp: int, hp: int, fp: int) -> int:
    """Elements of the lane-padded 2-layer MLP weights (W1+b1+W2+b2) —
    the resident set both FC kernels pin in VMEM."""
    return dp * hp + hp + hp * fp + fp


def gather_mlp_footprint_elems(t: int, k: int, dp: int, dc: int, hp: int,
                               fp: int) -> int:
    """Per-grid-step VMEM elements of the gather-MLP kernel at subset
    tile ``t``: double-buffered streamed blocks (raw tile + mask +
    centers), the (t·K, H/F) matmul intermediates, the output tile, and
    the resident weights.  Shared by :func:`gather_mlp_tile_plan`'s
    feasibility predicate, the ``repro.analysis`` kernel linter and the
    autotuner.  Elements are counted as the blocks declare them; Mosaic
    pads narrow minor dims (the (t, K, 1) mask, the (t, Dc) centers) to
    128 lanes in VMEM, which this model does not count."""
    streamed = 2 * t * (k * (dp + 1) + dc)       # double-buffered in
    inter = t * k * (hp + fp)                    # x@W1, h@W2
    out = t * fp
    return streamed + inter + out + mlp_weight_elems(dp, hp, fp)


def hub_reuse_footprint_elems(t: int, c: int, m: int, k: int, dp: int,
                              hp: int, fp: int) -> int:
    """Per-grid-step VMEM elements of the hub-reuse kernel at island
    tile ``t``; the one-hot gather's t² term is the binding constraint.
    Shared by :func:`hub_reuse_tile_plan` and ``repro.analysis``.  The
    (t·M·K, 1) slot column is counted unpadded, as for the gather mask."""
    streamed = 2 * t * (c * dp + m * k + m * fp)
    onehot = (t * m * k) * (t * c)
    inter = t * c * (hp + fp) + t * m * k * fp
    out = t * m * fp
    return streamed + onehot + inter + out + mlp_weight_elems(dp, hp, fp)


def largest_tile(limit: int, fits, base: int = SUBLANE) -> int:
    """Largest power-of-two multiple of ``base`` (capped at ``limit``) for
    which ``fits(tile) -> bool`` holds.  When even the base tile busts the
    budget, halve below it (down to 1) so an explicit tight budget is
    honored instead of silently exceeded.

    ``fits`` is a VMEM-bytes predicate built from the kernel's per-step
    buffer shapes; the scan is tiny and static (runs at trace time).
    """
    limit = max(limit, 1)
    t = min(base, limit)
    if not fits(t):
        while t > 1 and not fits(t):
            t //= 2
        return max(t, 1)
    best = t
    t *= 2
    while t <= limit:
        if not fits(t):
            break
        best = t
        t *= 2
    return best
