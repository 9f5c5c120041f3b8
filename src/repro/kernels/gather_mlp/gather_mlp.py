"""Pallas TPU kernel: fused center-normalize → MLP → max-pool (FC step).

The paper's FCU streams each gathered point subset through a 16×16 systolic
array (MLP = 98 % of FC FLOPs) and max-pools into the center.  TPU
adaptation: one fused kernel per subset tile —

    x   = [raw[..., :Dc] − center, raw[..., Dc:]]      (VPU)
    h   = relu(x @ W1 + b1) @ W2 + b2                  (MXU, f32 accum)
    out = max over K                                   (VPU)

so the (TS·K, H) intermediate never touches HBM.

Two entry points:

* ``gather_mlp_pallas`` — one cloud, grid over subset tiles (the original
  per-cloud kernel, kept for the eager path and vmap-of-kernels A/B).
* ``gather_mlp_batched_pallas`` — the natively batched serving kernel:
  grid ``(B, ⌈S/TS⌉)``, the batch folded into the grid so ONE pallas_call
  serves the whole cloud stack.  Weights use constant ``lambda b, i:
  (0, 0)`` index maps with ``dimension_semantics=("parallel",
  "arbitrary")`` so Mosaic keeps them VMEM-resident across the entire
  grid; the ``D``/``H``/``F`` lanes are zero-padded to 128-multiples
  before the call (zero lanes are exact no-ops through the matmuls) and
  the output is sliced back, so the MXU always sees aligned tiles.

VMEM budget per grid step (the ``TS`` heuristic solves for this; lane-
padded dims D'=⌈D/128⌉·128 etc., f32):
  streamed (double-buffered):  2·TS·(K·(D'+1) + Dc) · 4 B
      raw tile (TS, K, D') + mask (TS, K, 1) + centers (TS, Dc)
  intermediates:               TS·K·(H'+F') · 4 B      (x@W1, h@W2)
  resident weights:            (D'·H' + H' + H'·F' + F') · 4 B
  output tile:                 TS·F' · 4 B
e.g. TS=64, K=32, D'=H'=F'=128: 2·64·(32·129+3)·4 ≈ 2.1 MB streamed
+ 64·32·256·4 ≈ 2.1 MB intermediates + 130 KB weights < 8 MB default.
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
                                  gather_mlp_footprint_elems, largest_tile,
                                  pad_axis, round_up)

DEFAULT_SEMANTICS = ("parallel", "arbitrary")

BIG = 3.4e38


def _mlp_pool(raw, ctr, w1, b1, w2, b2, dc: int):
    """Shared kernel body: normalize → 2-layer MLP.  -> (TS, K, F).

    When the center spans every lane (``dc == D``: an EdgeConv row at a
    lane-aligned width) the subtract is the whole input; Mosaic refuses
    the empty slice ``raw[..., D:]`` that a concatenation would take."""
    ts, k, d = raw.shape
    if dc == d:
        x = raw - ctr[:, None, :]
    else:
        rel = raw[..., :dc] - ctr[:, None, :]
        x = jnp.concatenate([rel, raw[..., dc:]], axis=-1)    # (TS, K, D)
    x2 = x.reshape(ts * k, d)
    h = jax.lax.dot_general(x2, w1, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = jax.nn.relu(h + b1[None, :])
    y = jax.lax.dot_general(h, w2, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y = y + b2[None, :]
    return y.reshape(ts, k, -1)


def _gather_mlp_kernel(raw_ref, ctr_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                       out_ref, *, dc: int):
    y = _mlp_pool(raw_ref[...], ctr_ref[...], w1_ref[...], b1_ref[...],
                  w2_ref[...], b2_ref[...], dc)
    out_ref[...] = jnp.max(y, axis=1).astype(out_ref.dtype)


def _masked_max(y, mask):
    """Masked max-pool over K: y (TS, K, F); mask (TS, K, 1) int32
    (nonzero = live).  Invalid positions go to -BIG before the pool;
    subsets with zero live positions zero-fill instead of returning -BIG.

    The mask arrives with its trailing unit axis from the wrapper, so K
    sits on sublanes exactly as in ``y`` and the select broadcasts along
    lanes; Mosaic cannot expand a (TS, K) lane-major mask in-kernel."""
    pooled = jnp.max(jnp.where(mask != 0, y, -BIG), axis=1)
    return jnp.where(jnp.max(mask, axis=1) != 0, pooled, 0.0)


def _gather_mlp_masked_kernel(raw_ref, ctr_ref, mask_ref, w1_ref, b1_ref,
                              w2_ref, b2_ref, out_ref, *, dc: int):
    """Masked variant (ragged batches), see :func:`_masked_max`."""
    y = _mlp_pool(raw_ref[...], ctr_ref[...], w1_ref[...], b1_ref[...],
                  w2_ref[...], b2_ref[...], dc)
    out_ref[...] = _masked_max(y, mask_ref[...]).astype(out_ref.dtype)


def gather_mlp_pallas(raw: jnp.ndarray, centers: jnp.ndarray,
                      w1, b1, w2, b2, ts: int = 8,
                      interpret: bool = False, mask=None):
    """raw (S, K, D) gathered inputs; centers (S, Dc) subtracted from the
    leading Dc lanes; two-layer MLP; max over K.  -> (S, F_out).

    ``mask`` (S, K) int32 (nonzero = live) excludes padding positions
    from the pool; rows with no live position return zeros."""
    s, k, d = raw.shape
    dc = centers.shape[1]
    fout = w2.shape[1]
    hdim = w1.shape[1]
    ts = min(ts, s)
    weight_specs = [
        pl.BlockSpec((d, hdim), lambda i: (0, 0)),
        pl.BlockSpec((hdim,), lambda i: (0,)),
        pl.BlockSpec((hdim, fout), lambda i: (0, 0)),
        pl.BlockSpec((fout,), lambda i: (0,)),
    ]
    if mask is None:
        kern = functools.partial(_gather_mlp_kernel, dc=dc)
        in_specs = [
            pl.BlockSpec((ts, k, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((ts, dc), lambda i: (i, 0)),
            *weight_specs,
        ]
        args = (raw, centers, w1, b1, w2, b2)
    else:
        kern = functools.partial(_gather_mlp_masked_kernel, dc=dc)
        in_specs = [
            pl.BlockSpec((ts, k, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((ts, dc), lambda i: (i, 0)),
            pl.BlockSpec((ts, k, 1), lambda i: (i, 0, 0)),
            *weight_specs,
        ]
        args = (raw, centers, mask.astype(jnp.int32)[..., None],
                w1, b1, w2, b2)
    return pl.pallas_call(
        kern,
        grid=(pl.cdiv(s, ts),),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((ts, fout), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, fout), raw.dtype),
        interpret=interpret,
        name="gather_mlp_per_cloud",
    )(*args)


# ---- natively batched kernel: grid (B, ceil(S/TS)) --------------------------

def _gather_mlp_batched_kernel(raw_ref, ctr_ref, w1_ref, b1_ref, w2_ref,
                               b2_ref, out_ref, *, dc: int):
    """Blocks carry a leading singleton batch axis: raw (1, TS, K, D)."""
    y = _mlp_pool(raw_ref[...][0], ctr_ref[...][0], w1_ref[...],
                  b1_ref[...], w2_ref[...], b2_ref[...], dc)
    out_ref[...] = jnp.max(y, axis=1)[None].astype(out_ref.dtype)


def _gather_mlp_batched_masked_kernel(raw_ref, ctr_ref, mask_ref, w1_ref,
                                      b1_ref, w2_ref, b2_ref, out_ref,
                                      *, dc: int):
    y = _mlp_pool(raw_ref[...][0], ctr_ref[...][0], w1_ref[...],
                  b1_ref[...], w2_ref[...], b2_ref[...], dc)
    out_ref[...] = _masked_max(y, mask_ref[...][0])[None].astype(
        out_ref.dtype)


def gather_mlp_tile_plan(s: int, k: int, d: int, dc: int, hdim: int,
                         fout: int, ts: int | None = None,
                         vmem_budget_mb: float | None = None,
                         lanes: int | None = None,
                         dimension_semantics=None,
                         b: int | None = None) -> dict:
    """Resolve the batched kernel's tile plan: lane-padded dims and the
    subset tile ``TS`` that fills (but does not bust) the VMEM budget.

    Resolution order: explicit ``ts``/``lanes``/``dimension_semantics``
    (the ``kernel_kw`` knobs → ``provenance="override"``) > a
    ``repro.kernels.plans`` store hit for this ``(b, shape)`` cell
    (``"autotuned"``) > the VMEM heuristic at 128 lanes
    (``"heuristic"``).  A stale store entry — one whose recomputed
    footprint busts its own budget — warns and degrades to the
    heuristic instead of raising."""
    dims = {"b": b, "s": s, "k": k, "d": d, "dc": dc, "h": hdim, "f": fout}

    def build(ts, lanes, vmem_budget_mb, sem, provenance):
        lanes = LANE if lanes is None else int(lanes)
        mb = (DEFAULT_VMEM_BUDGET_MB if vmem_budget_mb is None
              else float(vmem_budget_mb))
        sem = DEFAULT_SEMANTICS if sem is None else tuple(sem)
        dp = round_up(d, lanes)
        hp = round_up(hdim, lanes)
        fp = round_up(fout, lanes)
        budget = int(mb * 2 ** 20)

        def fits(t: int) -> bool:
            return F32_BYTES * gather_mlp_footprint_elems(
                t, k, dp, dc, hp, fp) <= budget

        if ts is None:
            ts = largest_tile(s, fits)
        ts = max(1, min(int(ts), s))
        return {"ts": ts, "lanes": lanes, "d_pad": dp, "h_pad": hp,
                "f_pad": fp, "grid_tiles": pl.cdiv(s, ts),
                "vmem_budget_mb": mb,
                "dimension_semantics": sem,
                "footprint_bytes": F32_BYTES * gather_mlp_footprint_elems(
                    ts, k, dp, dc, hp, fp),
                "provenance": provenance}

    overridden = (ts is not None or lanes is not None
                  or dimension_semantics is not None)
    hit = None
    if not overridden and vmem_budget_mb is None and b is not None:
        hit = plans.lookup("gather_mlp", **dims)
    if hit is not None and hit.get("variant") == "vmap":
        # the measurement rejected the batched grid for this cell: the
        # dispatcher runs jax.vmap of the per-cloud kernel instead (no
        # lane padding, ts subsets per grid step per cloud)
        ts_v = max(1, min(int(hit.get("ts", 8)), s))
        plan = {"variant": "vmap", "ts": ts_v, "lanes": 1,
                "d_pad": d, "h_pad": hdim, "f_pad": fout,
                "grid_tiles": pl.cdiv(s, ts_v),
                "vmem_budget_mb": DEFAULT_VMEM_BUDGET_MB,
                "dimension_semantics": DEFAULT_SEMANTICS,
                "footprint_bytes": F32_BYTES * gather_mlp_footprint_elems(
                    ts_v, k, d, dc, hdim, fout),
                "provenance": "autotuned"}
        plans.note_plan("gather_mlp", dims, plan)
        return plan
    if hit is not None:
        plan = build(hit["ts"], hit.get("lanes"), hit.get("vmem_budget_mb"),
                     hit.get("dimension_semantics"), "autotuned")
        if plan["footprint_bytes"] > int(plan["vmem_budget_mb"] * 2 ** 20):
            warnings.warn(
                f"stale tile plan for {plans.plan_key('gather_mlp', dims)}: "
                f"footprint {plan['footprint_bytes']} B busts its "
                f"{plan['vmem_budget_mb']} MB budget; using the heuristic "
                f"(re-run python -m repro.launch.autotune)",
                RuntimeWarning, stacklevel=2)
            plan = build(None, None, None, None, "heuristic")
    else:
        plan = build(ts, lanes, vmem_budget_mb, dimension_semantics,
                     "override" if overridden else "heuristic")
    plans.note_plan("gather_mlp", dims, plan)
    return plan


def gather_mlp_batched_pallas(raw: jnp.ndarray, centers: jnp.ndarray,
                              w1, b1, w2, b2, ts: int | None = None,
                              vmem_budget_mb: float | None = None,
                              lanes: int | None = None,
                              dimension_semantics=None,
                              interpret: bool = False, mask=None):
    """Natively batched gather-MLP: raw (B, S, K, D), centers (B, S, Dc),
    optional mask (B, S, K).  -> (B, S, F_out) in ONE pallas_call with
    grid (B, ⌈S/TS⌉).

    Weights ride constant index maps (VMEM-resident across the grid);
    D/H/F are zero-padded to ``lanes``-multiples (sliced back on
    return); ``ts`` / ``vmem_budget_mb`` / ``lanes`` /
    ``dimension_semantics`` are the ``kernel_kw`` knobs — left None,
    the plan comes from the autotuned store (on a hit) or the VMEM
    heuristic (see :func:`gather_mlp_tile_plan`)."""
    b, s, k, d = raw.shape
    dc = centers.shape[2]
    hdim, fout = w1.shape[1], w2.shape[1]
    plan = gather_mlp_tile_plan(s, k, d, dc, hdim, fout, ts=ts,
                                vmem_budget_mb=vmem_budget_mb,
                                lanes=lanes,
                                dimension_semantics=dimension_semantics,
                                b=b)
    if plan.get("variant") == "vmap":
        # measured winner for this cell is the per-cloud dispatch: B
        # logical per-cloud programs via the pallas batching rule
        per_cloud = functools.partial(gather_mlp_pallas, w1=w1, b1=b1,
                                      w2=w2, b2=b2, ts=plan["ts"],
                                      interpret=interpret)
        if mask is None:
            return jax.vmap(lambda r, c: per_cloud(r, c))(raw, centers)
        return jax.vmap(lambda r, c, mk: per_cloud(r, c, mask=mk))(
            raw, centers, mask)
    ts = plan["ts"]
    dp, hp, fp = plan["d_pad"], plan["h_pad"], plan["f_pad"]

    raw = pad_axis(raw, 3, dp)
    w1 = pad_axis(pad_axis(w1, 1, hp), 0, dp)
    b1 = pad_axis(b1, 0, hp)
    w2 = pad_axis(pad_axis(w2, 1, fp), 0, hp)
    b2 = pad_axis(b2, 0, fp)

    weight_specs = [
        pl.BlockSpec((dp, hp), lambda bi, i: (0, 0)),
        pl.BlockSpec((hp,), lambda bi, i: (0,)),
        pl.BlockSpec((hp, fp), lambda bi, i: (0, 0)),
        pl.BlockSpec((fp,), lambda bi, i: (0,)),
    ]
    data_specs = [
        pl.BlockSpec((1, ts, k, dp), lambda bi, i: (bi, i, 0, 0)),
        pl.BlockSpec((1, ts, dc), lambda bi, i: (bi, i, 0)),
    ]
    if mask is None:
        kern = functools.partial(_gather_mlp_batched_kernel, dc=dc)
        in_specs = data_specs + weight_specs
        args = (raw, centers, w1, b1, w2, b2)
    else:
        kern = functools.partial(_gather_mlp_batched_masked_kernel, dc=dc)
        in_specs = (data_specs
                    + [pl.BlockSpec((1, ts, k, 1),
                                    lambda bi, i: (bi, i, 0, 0))]
                    + weight_specs)
        args = (raw, centers, mask.astype(jnp.int32)[..., None],
                w1, b1, w2, b2)
    out = pl.pallas_call(
        kern,
        grid=(b, pl.cdiv(s, ts)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, ts, fp), lambda bi, i: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, fp), raw.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=tuple(plan["dimension_semantics"])),
        interpret=interpret,
        name="gather_mlp",
    )(*args)
    return out[..., :fout]
