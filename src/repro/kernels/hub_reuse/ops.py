"""Jitted public wrappers for the hub_reuse kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import plans, resolve_interpret
from .hub_reuse import (hub_reuse_batched_pallas, hub_reuse_pallas,
                        hub_reuse_tile_plan)
from .ref import hub_reuse_ref


@partial(jax.jit, static_argnames=("interpret",))
def hub_reuse(pool_in, slot, comp, w1, b1, w2, b2,
              interpret: bool | None = None, live=None):
    """Pool-MLP + compensated reuse-gather + masked max-pool, one cloud.
    ``live`` (H, M, K) bool/int (None = all resident) additionally masks
    positions whose cache entry is not actually resident (ragged
    batches)."""
    return hub_reuse_pallas(pool_in, slot, comp, w1, b1, w2, b2,
                            interpret=resolve_interpret(interpret),
                            live=live)


@partial(jax.jit, static_argnames=("th", "vmem_budget_mb", "lanes",
                                   "dimension_semantics", "interpret"))
def hub_reuse_batched(pool_in, slot, comp, w1, b1, w2, b2,
                      th: int | None = None,
                      vmem_budget_mb: float | None = None,
                      lanes: int | None = None,
                      dimension_semantics: tuple | None = None,
                      interpret: bool | None = None, live=None):
    """Natively batched hub-reuse: (B, H, C, D) → (B, H, M, F_out) through
    ONE pallas_call with grid (B, ⌈H/TH⌉); TH islands share one pool
    matmul and one offset-one-hot reuse matmul per step, weights stay
    VMEM-resident and D/H/F lanes are padded to ``lanes`` multiples.
    ``th`` / ``vmem_budget_mb`` / ``lanes`` / ``dimension_semantics``
    are the ``kernel_kw`` knobs (all None = the autotuned plan store,
    else the VMEM-budget heuristic); ``live`` (B, H, M, K) as in
    :func:`hub_reuse`."""
    return hub_reuse_batched_pallas(
        pool_in, slot, comp, w1, b1, w2, b2, th=th,
        vmem_budget_mb=vmem_budget_mb, lanes=lanes,
        dimension_semantics=dimension_semantics,
        interpret=resolve_interpret(interpret), live=live)


# the tile plan resolves inside the trace: a plan-store mutation (or a
# plans.bypass() boundary) must drop traces made under the old plan
plans.register_cache_clearer(hub_reuse_batched.clear_cache)


__all__ = ["hub_reuse", "hub_reuse_batched", "hub_reuse_ref",
           "hub_reuse_tile_plan"]
