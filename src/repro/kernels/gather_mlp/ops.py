"""Jitted public wrappers for the fused gather-MLP-pool kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import plans, resolve_interpret
from .gather_mlp import (gather_mlp_batched_pallas, gather_mlp_pallas,
                         gather_mlp_tile_plan)
from .ref import gather_mlp_ref


@partial(jax.jit, static_argnames=("ts", "interpret"))
def gather_mlp(raw, centers, w1, b1, w2, b2, ts: int = 8,
               interpret: bool | None = None, mask=None):
    """Fused normalize → MLP → max-pool, one cloud.  ``mask`` (S, K)
    bool/int (None = all live) excludes ragged padding positions from the
    pool; rows with zero live positions return zeros instead of -BIG."""
    return gather_mlp_pallas(raw, centers, w1, b1, w2, b2, ts=ts,
                             interpret=resolve_interpret(interpret),
                             mask=mask)


@partial(jax.jit, static_argnames=("ts", "vmem_budget_mb", "lanes",
                                   "dimension_semantics", "interpret"))
def gather_mlp_batched(raw, centers, w1, b1, w2, b2, ts: int | None = None,
                       vmem_budget_mb: float | None = None,
                       lanes: int | None = None,
                       dimension_semantics: tuple | None = None,
                       interpret: bool | None = None, mask=None):
    """Natively batched gather-MLP: (B, S, K, D) → (B, S, F_out) through
    ONE pallas_call with grid (B, ⌈S/TS⌉); weights stay VMEM-resident
    across the whole grid and D/H/F lanes are padded to ``lanes``
    multiples.  ``ts`` / ``vmem_budget_mb`` / ``lanes`` /
    ``dimension_semantics`` are the ``kernel_kw`` knobs (all None = the
    autotuned plan store, else the VMEM-budget heuristic); ``mask``
    (B, S, K) as in :func:`gather_mlp`."""
    return gather_mlp_batched_pallas(
        raw, centers, w1, b1, w2, b2, ts=ts,
        vmem_budget_mb=vmem_budget_mb, lanes=lanes,
        dimension_semantics=dimension_semantics,
        interpret=resolve_interpret(interpret), mask=mask)


# the tile plan resolves inside the trace: a plan-store mutation (or a
# plans.bypass() boundary) must drop traces made under the old plan
plans.register_cache_clearer(gather_mlp_batched.clear_cache)


__all__ = ["gather_mlp", "gather_mlp_batched", "gather_mlp_ref",
           "gather_mlp_tile_plan"]
