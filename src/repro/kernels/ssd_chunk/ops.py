"""Jitted public wrapper for the SSD intra-chunk kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import resolve_interpret
from .ref import ssd_chunk_ref
from .ssd_chunk import ssd_chunk_pallas


@partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x, B, C, dt, cum, interpret: bool | None = None):
    return ssd_chunk_pallas(x, B, C, dt, cum,
                            interpret=resolve_interpret(interpret))


__all__ = ["ssd_chunk", "ssd_chunk_ref"]
