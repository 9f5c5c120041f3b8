"""Pallas TPU kernel: islandized FC — pool-MLP + compensated reuse-gather.

The Islandization Unit's datapath (paper Fig. 13/14):

  1. pool MLP: the island's Hub-Cache contents (C unique points, hub-
     relative inputs) go through the 2-layer MLP once          (MXU)
  2. reuse gather: every (subset, k) position fetches its cache slot.
     TPU adaptation: the gather is a ONE-HOT MATMUL (M·K, C) @ (C, F) —
     a systolic-friendly reuse of the MXU instead of the FPGA's BRAM
     random port                                              (MXU)
  3. masked max-pool over K                                    (VPU)
  4. delta compensation: + comp[subset], constant over K, so
     added after the pool                                      (VPU)

Overflow (never-cached) positions are computed by the gather_mlp kernel
outside and merged with an elementwise max (max-pool commutes), so this
kernel touches exactly the deduplicated workload — the paper's compute
saving is structural, not simulated.  A subset with zero live positions
returns the merge identity ``-BIG``; the merge boundary in
``core.pipeline`` zero-fills any row that stayed at the sentinel.

Two entry points:

* ``hub_reuse_pallas`` — one cloud, one island per grid step (kept for
  the eager path and the vmap-of-kernels A/B).
* ``hub_reuse_batched_pallas`` — the natively batched serving kernel:
  grid ``(B, ⌈H/TH⌉)`` with a new island-tile axis ``TH``, so ONE
  pallas_call serves the whole cloud stack.  The TH islands of a step
  share one (TH·C, D')@(D', H') pool matmul and one offset-one-hot
  (TH·M·K, TH·C)@(TH·C, F') reuse matmul — both fully lane-aligned
  (D/H/F zero-padded to 128-multiples, sliced back after).  Weights ride
  constant ``lambda b, j: (0, 0)`` index maps with
  ``dimension_semantics=("parallel", "arbitrary")`` → VMEM-resident
  across the whole grid.

VMEM budget per grid step (the ``TH`` heuristic solves for this; lane-
padded D', H', F'; f32):
  streamed (double-buffered):  2·TH·(C·D' + M·K + M·F') · 4 B
      pool (TH, C, D') + slot column (TH·M·K, 1) + comp (TH, M, F')
  one-hot + gathered:          (TH·M·K)·(TH·C) + TH·M·K·F') · 4 B
  pool MLP intermediates:      TH·C·(H' + F') · 4 B
  resident weights:            (D'·H' + H' + H'·F' + F') · 4 B
  output tile:                 TH·M·F' · 4 B
The one-hot term grows with TH², which is what caps TH (e.g. TH=4,
M=64, K=32, C=64: one-hot 8192·256·4 = 8 MB alone → TH=2 at the 8 MB
default).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import plans
from repro.kernels.tiling import (DEFAULT_VMEM_BUDGET_MB, F32_BYTES, LANE,
                                  hub_reuse_footprint_elems, largest_tile,
                                  pad_axis, round_up)

DEFAULT_SEMANTICS = ("parallel", "arbitrary")

BIG = 3.4e38


def _reuse_pool(pool, slot, comp, w1, b1, w2, b2, *, c: int, k: int):
    """Shared kernel body for T islands: pool MLP + one-hot reuse-gather
    + masked max-pool + Δ-comp.

    pool (T·C, D) hub-relative inputs; slot (T·M·K, 1) int32 cache slots
    (−1 = not live); comp (T·M, F).  -> (T·M, F), −BIG where a subset has
    no live position.

    The T pool MLPs run as one (T·C, D) matmul; the T reuse gathers run
    as one offset-one-hot (T·M·K, T·C) matmul — island j's slots map to
    columns [j·C, (j+1)·C), dead slots hit no column.  ``slot`` arrives
    as a column (the wrapper flattens it, with the liveness mask folded
    in), so every mask broadcasts along lanes: Mosaic cannot move a
    lane-major (M, K) tile onto sublanes in-kernel.  Δ-comp is added after
    the pool: it is constant over K and rounding is monotone, so
    max_k(g + comp) == max_k(g) + comp exactly."""
    r = slot.shape[0]
    mk = r * c // pool.shape[0]                       # M·K per island
    h = jax.lax.dot_general(pool, w1, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = jax.nn.relu(h + b1[None, :])
    y = jax.lax.dot_general(h, w2, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + b2[None, :]                                # (T*C, F)

    live = slot >= 0                                   # (T*M*K, 1)
    offset = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0) // mk * c
    col = jnp.where(live, slot + offset, -1)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (r, pool.shape[0]), 1)
              == col).astype(jnp.float32)              # (T*M*K, T*C)
    gathered = jax.lax.dot_general(
        onehot, y, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # (T*M*K, F) MXU
    gathered = jnp.where(live, gathered, -BIG)
    pooled = jnp.max(gathered.reshape(r // k, k, -1), axis=1)
    return jnp.where(pooled > -BIG, pooled + comp, -BIG)


def _hub_reuse_kernel(pool_ref, slot_ref, comp_ref, w1_ref, b1_ref,
                      w2_ref, b2_ref, out_ref, *, k: int):
    """pool_ref (1, C, D) hub-relative inputs; slot_ref (M*K, 1) int32;
    comp_ref (1, M, F); out_ref (1, M, F)."""
    _, c, _ = pool_ref.shape
    out = _reuse_pool(pool_ref[0], slot_ref[...], comp_ref[0], w1_ref[...],
                      b1_ref[...], w2_ref[...], b2_ref[...], c=c, k=k)
    out_ref[...] = out[None].astype(out_ref.dtype)


def _slot_column(slot, live):
    """Fold the optional liveness mask into the slots (dead = −1) and
    flatten the trailing (H, M, K) axes to one (H·M·K, 1) column."""
    if live is not None:
        slot = jnp.where(live != 0, slot, -1)
    return slot.astype(jnp.int32).reshape(slot.shape[:-3] + (-1, 1))


def hub_reuse_pallas(pool_in: jnp.ndarray, slot: jnp.ndarray,
                     comp: jnp.ndarray, w1, b1, w2, b2,
                     interpret: bool = False, live=None):
    """pool_in (H, C, D); slot (H, M, K) int32 (-1 = not cached);
    comp (H, M, F) per-subset delta compensation.  -> (H, M, F) pooled
    reuse partials (−BIG where a subset has no cached positions).
    ``live`` (H, M, K) int32 (nonzero = cache entry resident) composes
    with ``slot >= 0``."""
    hn, c, d = pool_in.shape
    _, m, k = slot.shape
    hdim = w1.shape[1]
    fout = w2.shape[1]
    in_specs = [
        pl.BlockSpec((1, c, d), lambda i: (i, 0, 0)),
        pl.BlockSpec((m * k, 1), lambda i: (i, 0)),
        pl.BlockSpec((1, m, fout), lambda i: (i, 0, 0)),
        pl.BlockSpec((d, hdim), lambda i: (0, 0)),
        pl.BlockSpec((hdim,), lambda i: (0,)),
        pl.BlockSpec((hdim, fout), lambda i: (0, 0)),
        pl.BlockSpec((fout,), lambda i: (0,)),
    ]
    return pl.pallas_call(
        functools.partial(_hub_reuse_kernel, k=k),
        grid=(hn,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, m, fout), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((hn, m, fout), pool_in.dtype),
        interpret=interpret,
        name="hub_reuse_per_cloud",
    )(pool_in, _slot_column(slot, live), comp, w1, b1, w2, b2)


# ---- natively batched kernel: grid (B, ceil(H/TH)) --------------------------

def _hub_reuse_batched_kernel(pool_ref, slot_ref, comp_ref, w1_ref, b1_ref,
                              w2_ref, b2_ref, out_ref, *, hn: int, k: int):
    """TH islands per step.  Blocks carry a leading singleton batch axis:
    pool (1, TH, C, D), slot (1, TH*M*K, 1), comp (1, TH, M, F).

    When TH does not divide H, the last step's out-of-range islands read
    padding (NaN in interpret mode) — their pool rows are zeroed before
    the shared one-hot matmul so 0·NaN can't contaminate real islands
    (their own outputs are clipped on write anyway)."""
    _, th, c, d = pool_ref.shape
    _, _, m, f = comp_ref.shape
    pool = pool_ref[0].reshape(th * c, d)
    island_of_row = jax.lax.broadcasted_iota(jnp.int32, (th * c, 1), 0) // c
    in_range = pl.program_id(1) * th + island_of_row < hn
    pool = jnp.where(in_range, pool, 0.0)
    out = _reuse_pool(pool, slot_ref[0], comp_ref[0].reshape(th * m, f),
                      w1_ref[...], b1_ref[...], w2_ref[...], b2_ref[...],
                      c=c, k=k)
    out_ref[...] = out.reshape(1, th, m, f).astype(out_ref.dtype)


def hub_reuse_tile_plan(hn: int, c: int, m: int, k: int, d: int, hdim: int,
                        fout: int, th: int | None = None,
                        vmem_budget_mb: float | None = None,
                        lanes: int | None = None,
                        dimension_semantics=None,
                        b: int | None = None) -> dict:
    """Resolve the batched kernel's tile plan: lane-padded dims and the
    island tile ``TH`` under the VMEM budget (the one-hot's TH² term is
    the binding constraint).

    Resolution order mirrors :func:`gather_mlp_tile_plan`: explicit
    ``th``/``lanes``/``dimension_semantics`` ("override") > a
    ``repro.kernels.plans`` store hit for this ``(b, shape)`` cell
    ("autotuned") > the VMEM heuristic at 128 lanes ("heuristic"); a
    stale store entry warns and degrades to the heuristic."""
    dims = {"b": b, "hn": hn, "c": c, "m": m, "k": k, "d": d, "h": hdim,
            "f": fout}

    def build(th, lanes, vmem_budget_mb, sem, provenance):
        lanes = LANE if lanes is None else int(lanes)
        mb = (DEFAULT_VMEM_BUDGET_MB if vmem_budget_mb is None
              else float(vmem_budget_mb))
        sem = DEFAULT_SEMANTICS if sem is None else tuple(sem)
        dp = round_up(d, lanes)
        hp = round_up(hdim, lanes)
        fp = round_up(fout, lanes)
        budget = int(mb * 2 ** 20)

        def fits(t: int) -> bool:
            return F32_BYTES * hub_reuse_footprint_elems(
                t, c, m, k, dp, hp, fp) <= budget

        if th is None:
            th = largest_tile(hn, fits, base=1)
        th = max(1, min(int(th), hn))
        return {"th": th, "lanes": lanes, "d_pad": dp, "h_pad": hp,
                "f_pad": fp, "grid_tiles": pl.cdiv(hn, th),
                "vmem_budget_mb": mb,
                "dimension_semantics": sem,
                "footprint_bytes": F32_BYTES * hub_reuse_footprint_elems(
                    th, c, m, k, dp, hp, fp),
                "provenance": provenance}

    overridden = (th is not None or lanes is not None
                  or dimension_semantics is not None)
    hit = None
    if not overridden and vmem_budget_mb is None and b is not None:
        hit = plans.lookup("hub_reuse", **dims)
    if hit is not None and hit.get("variant") == "vmap":
        # the measurement rejected the batched grid for this cell (the
        # common case: a handful of islands, where the TH² one-hot and
        # lane padding cost more than they amortize): dispatch jax.vmap
        # of the per-cloud kernel — one island per grid step, no padding
        plan = {"variant": "vmap", "th": 1, "lanes": 1,
                "d_pad": d, "h_pad": hdim, "f_pad": fout,
                "grid_tiles": hn,
                "vmem_budget_mb": DEFAULT_VMEM_BUDGET_MB,
                "dimension_semantics": DEFAULT_SEMANTICS,
                "footprint_bytes": F32_BYTES * hub_reuse_footprint_elems(
                    1, c, m, k, d, hdim, fout),
                "provenance": "autotuned"}
        plans.note_plan("hub_reuse", dims, plan)
        return plan
    if hit is not None:
        plan = build(hit["th"], hit.get("lanes"), hit.get("vmem_budget_mb"),
                     hit.get("dimension_semantics"), "autotuned")
        if plan["footprint_bytes"] > int(plan["vmem_budget_mb"] * 2 ** 20):
            warnings.warn(
                f"stale tile plan for {plans.plan_key('hub_reuse', dims)}: "
                f"footprint {plan['footprint_bytes']} B busts its "
                f"{plan['vmem_budget_mb']} MB budget; using the heuristic "
                f"(re-run python -m repro.launch.autotune)",
                RuntimeWarning, stacklevel=2)
            plan = build(None, None, None, None, "heuristic")
    else:
        plan = build(th, lanes, vmem_budget_mb, dimension_semantics,
                     "override" if overridden else "heuristic")
    plans.note_plan("hub_reuse", dims, plan)
    return plan


def hub_reuse_batched_pallas(pool_in: jnp.ndarray, slot: jnp.ndarray,
                             comp: jnp.ndarray, w1, b1, w2, b2,
                             th: int | None = None,
                             vmem_budget_mb: float | None = None,
                             lanes: int | None = None,
                             dimension_semantics=None,
                             interpret: bool = False, live=None):
    """Natively batched hub-reuse: pool_in (B, H, C, D), slot (B, H, M, K),
    comp (B, H, M, F), optional live (B, H, M, K).  -> (B, H, M, F_out) in
    ONE pallas_call with grid (B, ⌈H/TH⌉).

    Weights ride constant index maps (VMEM-resident across the grid);
    D/H/F are zero-padded to ``lanes``-multiples (sliced back on
    return); ``th`` / ``vmem_budget_mb`` / ``lanes`` /
    ``dimension_semantics`` are the ``kernel_kw`` knobs — left None,
    the plan comes from the autotuned store (on a hit) or the VMEM
    heuristic (see :func:`hub_reuse_tile_plan`)."""
    b, hn, c, d = pool_in.shape
    _, _, m, k = slot.shape
    hdim, fout = w1.shape[1], w2.shape[1]
    plan = hub_reuse_tile_plan(hn, c, m, k, d, hdim, fout, th=th,
                               vmem_budget_mb=vmem_budget_mb, lanes=lanes,
                               dimension_semantics=dimension_semantics,
                               b=b)
    if plan.get("variant") == "vmap":
        # measured winner for this cell is the per-cloud dispatch: B
        # logical per-cloud programs via the pallas batching rule
        per_cloud = functools.partial(hub_reuse_pallas, w1=w1, b1=b1,
                                      w2=w2, b2=b2, interpret=interpret)
        if live is None:
            return jax.vmap(lambda p, sl, cp: per_cloud(p, sl, cp))(
                pool_in, slot, comp)
        return jax.vmap(lambda p, sl, cp, lv: per_cloud(p, sl, cp, live=lv))(
            pool_in, slot, comp, live)
    th = plan["th"]
    dp, hp, fp = plan["d_pad"], plan["h_pad"], plan["f_pad"]

    pool_in = pad_axis(pool_in, 3, dp)
    comp = pad_axis(comp, 3, fp)
    w1 = pad_axis(pad_axis(w1, 1, hp), 0, dp)
    b1 = pad_axis(b1, 0, hp)
    w2 = pad_axis(pad_axis(w2, 1, fp), 0, hp)
    b2 = pad_axis(b2, 0, fp)

    weight_specs = [
        pl.BlockSpec((dp, hp), lambda bi, j: (0, 0)),
        pl.BlockSpec((hp,), lambda bi, j: (0,)),
        pl.BlockSpec((hp, fp), lambda bi, j: (0, 0)),
        pl.BlockSpec((fp,), lambda bi, j: (0,)),
    ]
    data_specs = [
        pl.BlockSpec((1, th, c, dp), lambda bi, j: (bi, j, 0, 0)),
        pl.BlockSpec((1, th * m * k, 1), lambda bi, j: (bi, j, 0)),
        pl.BlockSpec((1, th, m, fp), lambda bi, j: (bi, j, 0, 0)),
    ]
    out = pl.pallas_call(
        functools.partial(_hub_reuse_batched_kernel, hn=hn, k=k),
        grid=(b, pl.cdiv(hn, th)),
        in_specs=data_specs + weight_specs,
        out_specs=pl.BlockSpec((1, th, m, fp), lambda bi, j: (bi, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hn, m, fp), pool_in.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=tuple(plan["dimension_semantics"])),
        interpret=interpret,
        name="hub_reuse",
    )(pool_in, _slot_column(slot, live), comp, w1, b1, w2, b2)
    return out[..., :fout]
