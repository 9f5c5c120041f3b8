"""Roofline arithmetic shared by every family.

The operations and bytes of a network are counted by its family's module
(``bench/families/<family>.py``), from a configuration's shapes, with the
helpers here.  They count the work the algorithm needs, never how an
implementation does it (no lane padding, no one-hot gathers, no
split-sign embeddings).  A call is ``{"flops": int, "bytes": int}``.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def mlp_flops(dims) -> int:
    """Multiply-adds of one row through the dense layers ``dims``, x2."""
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def mlp_weight_bytes(dims) -> int:
    """Float32 weights and biases of the dense layers ``dims``."""
    return sum((a * b + b) * F32 for a, b in zip(dims[:-1], dims[1:]))


def roofline_seconds(calls: list[dict], peaks: dict) -> float:
    """Least time the chip needs for these calls: each call bound by the
    larger of its operations over peak FLOP/s and its bytes over peak
    bandwidth."""
    return sum(max(c["flops"] / peaks["flops_per_s"],
                   c["bytes"] / peaks["hbm_bytes_per_s"]) for c in calls)
