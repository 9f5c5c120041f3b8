"""Jitted public wrapper for the KNN kernel.

On CPU the kernel runs under ``interpret=True``; on TPU it compiles
through Mosaic (see :func:`repro.kernels.resolve_interpret`).  ``knn()``
is the drop-in used by ``core.neighbor`` when ``use_pallas=True``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from .knn import knn_pallas
from .ref import knn_ref


@partial(jax.jit, static_argnames=("k", "tc", "tp", "interpret"))
def knn(centers: jnp.ndarray, points: jnp.ndarray, k: int,
        tc: int = 128, tp: int = 512, interpret: bool | None = None):
    """(S,3),(N,3) -> ((S,k) sq-dists, (S,k) int32 indices)."""
    return knn_pallas(centers, points, k, tc=tc, tp=tp,
                      interpret=resolve_interpret(interpret))


__all__ = ["knn", "knn_ref"]
