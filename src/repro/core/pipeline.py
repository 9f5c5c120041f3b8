"""The L-PCN Building Block: Data Structuring → Islandization → Feature
Computation (paper Fig. 2/5/13), as one composable JAX module.

``lpcn_block`` runs a full PCN building block for one cloud and returns
(center_xyz, center_features, workload report).  Execution modes:

  * ``traditional`` — every subset fully fetched + computed (the baseline
    every accelerator in Fig. 16 uses for its FCU);
  * ``lpcn`` — Octree-based Islandization + Hub-based Scheduling: pool MLP
    once per island (hub-relative), compensated reuse for cached positions,
    compact overflow buffer for the rest.  FLOPs genuinely shrink: the MLP
    runs on (H·C + overflow_budget + fallback) points, not S·K.

Block kinds:  ``sa``  — Set Abstraction (PointNet++/PointNeXt/PointVector),
MLP input [p − c, f];  ``edge`` — EdgeConv (DGCNN), MLP input [f_j − f_i,
f_i].  Delta compensation handles both (delta_comp.py).

The Pallas kernels (kernels/gather_mlp, kernels/hub_reuse) implement the
same two dataflows for the MXU; this file is their jnp oracle and the
default CPU path.

Each stage runs under a flat ``jax.named_scope`` (``pcn.octree``,
``pcn.sample``, ``pcn.neighbors``, ``pcn.islandize``, ``pcn.schedule``,
``pcn.reuse_inputs``, ``pcn.overflow``, ``pcn.dense_inputs``; the engine
adds ``pcn.head``).  A scope only names the ops in their metadata, which
a profiler trace carries as each device op's ``tf_op``; no stage scope
encloses another.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable

import jax
import jax.numpy as jnp

from . import octree as oct
from .delta_comp import compensation
from .hub_schedule import Schedule, build_schedule
from .islandize import Islands, islandize
from .mlp import MLP, apply_mlp, post_pool_activation
from .registry import FC_BACKENDS, NEIGHBORS, SAMPLERS, get_fc_backend
from .workload import WorkloadReport, analyze

BIG = 3.4e38


@dataclass(frozen=True)
class LPCNConfig:
    """Hyper-parameters of one building block (paper defaults)."""
    n_centers: int = 512
    k: int = 32
    sampler: str = "fps"              # any registered sampler
    neighbor: str = "pointacc"        # any registered neighbor method
    radius: float = 0.2               # ball query radius
    mode: str = "lpcn"                # traditional | lpcn
    block_kind: str = "sa"            # sa | edge
    island_size: int = 32             # subsets per island (paper default)
    island_capacity: int = 64         # island-list rows (2x headroom)
    cache_capacity_x: float = 2.0     # hub cache = x * k (paper: 2x)
    compensation: str = "linear"      # linear | mlp
    octree_level: int = 4
    hub_select: str = "random"
    overflow_frac: float = 0.5        # compact overflow buffer / (M*K)
    fc_backend: str = "reference"     # any registered FC backend

    @property
    def cache_capacity(self) -> int:
        return int(self.cache_capacity_x * self.k)


@dataclass(frozen=True)
class FCBackend:
    """A Feature-Computation dataflow implementation (the paper's FCU).

    ``dense`` is the traditional path — subset-normalize, MLP, max-pool —
    returning (S, F_out) pooled pre-activation features.  ``reuse`` is the
    Islandization Unit's pool-MLP + compensated reuse-gather returning
    (H, M, F_out) per-subset pooled reuse partials, ``-BIG`` where a subset
    has no cached position.  Both must be jit/vmap-safe; the "reference"
    backend is pure jnp, the "pallas" backend (repro.engine.fc) routes the
    same dataflows through the kernels in repro.kernels.

    dense(mlp, kind, xyz, feats, nbr_idx, centers_xyz, center_feats,
          nbr_valid)
    reuse(mlp, pool_in, slot, comp, live)

    ``dense_batched`` / ``reuse_batched`` (optional) are the natively
    batched entry points used by the batch-first engine: the same
    dataflows with a leading (B,) axis on every array operand, expected
    to present the whole cloud stack to the accelerator as ONE schedule
    (e.g. one pallas_call with the batch folded into the kernel grid).
    They additionally take ``kernel_kw`` — an opaque dict of tuning knobs
    (tile sizes, VMEM budget) threaded down from ``engine.apply``.  When
    None, the engine falls back to ``jax.vmap`` of the per-cloud entry
    (the vmap-of-kernels path, kept for A/B measurement).

    dense_batched(mlp, kind, xyz, feats, nbr_idx, centers_xyz,
                  center_feats, nbr_valid, kernel_kw=None)
    reuse_batched(mlp, pool_in, slot, comp, live, kernel_kw=None)

    Ragged-batch contract: ``nbr_valid`` (S, K) bool (None = all valid)
    masks neighbor slots out of the max-pool (-> -BIG before the pool);
    a subset with zero valid slots yields an all-zero feature row, never
    -BIG/NaN.  ``reuse`` treats ``slot < 0`` as empty and additionally
    ANDs the optional ``live`` (H, M, K) mask (cache-slot liveness).
    """
    name: str
    dense: Callable
    reuse: Callable
    dense_batched: Callable | None = None
    reuse_batched: Callable | None = None


def _per_shard(fn, mesh, data, mlp):
    """``fn(data, mlp)`` on one device, or once per data shard of
    ``mesh``: the (B, …) ``data`` leaves and the result split along B,
    ``mlp`` whole on every device.  Mosaic kernels cannot be partitioned
    automatically, so a mesh-sharded forward hands each device its own
    shard of the cloud stack."""
    if mesh is None:
        return fn(data, mlp)
    from repro.dist.sharding import on_data_shards
    return on_data_shards(fn, mesh, data, mlp)


def dense_batched(backend: FCBackend, mlp, kind, xyz, feats, nbr_idx,
                  centers_xyz, center_feats=None, nbr_valid=None,
                  kernel_kw=None, mesh=None):
    """Batched dense FC through ``backend``: native entry when available,
    else vmap of the per-cloud entry (one kernel dispatch per cloud).
    ``mesh`` (None = one device) runs it per data shard."""
    def run(data, mlp):
        xyz, feats, nbr_idx, centers_xyz, center_feats, nbr_valid = data
        if backend.dense_batched is not None:
            return backend.dense_batched(mlp, kind, xyz, feats, nbr_idx,
                                         centers_xyz, center_feats,
                                         nbr_valid, kernel_kw=kernel_kw)
        return jax.vmap(
            lambda x, f, n, c, cf, nv: backend.dense(mlp, kind, x, f, n, c,
                                                     cf, nv),
            in_axes=(0, 0, 0, 0, None if center_feats is None else 0,
                     None if nbr_valid is None else 0),
        )(xyz, feats, nbr_idx, centers_xyz, center_feats, nbr_valid)

    return _per_shard(run, mesh, (xyz, feats, nbr_idx, centers_xyz,
                                  center_feats, nbr_valid), mlp)


def reuse_batched(backend: FCBackend, mlp, pool_in, slot, comp, live=None,
                  kernel_kw=None, mesh=None):
    """Batched reuse FC through ``backend``: native entry when available,
    else vmap of the per-cloud entry.  ``mesh`` as in
    :func:`dense_batched`."""
    def run(data, mlp):
        pool_in, slot, comp, live = data
        if backend.reuse_batched is not None:
            return backend.reuse_batched(mlp, pool_in, slot, comp, live,
                                         kernel_kw=kernel_kw)
        return jax.vmap(
            lambda p, s, c, l: backend.reuse(mlp, p, s, c, l),
            in_axes=(0, 0, 0, None if live is None else 0),
        )(pool_in, slot, comp, live)

    return _per_shard(run, mesh, (pool_in, slot, comp, live), mlp)


def data_structuring(cfg: LPCNConfig, xyz: jnp.ndarray,
                     key: jax.Array, n_valid=None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """DS step: sample centers, gather neighbors (both registry-resolved).
    Returns (center_idx (S,), nbr_idx (S, K)).

    ``n_valid`` (traced count or None) marks rows >= n_valid of ``xyz``
    as padding: the octree sorts them last, samplers never select them
    and neighbor methods never return them (unfillable slots are -1).
    The kwarg is forwarded to the registered components only when set;
    note the batched engine always sets it (a traced per-cloud count), so
    components registered for use through ``engine.apply`` must accept
    ``n_valid`` — a clear TypeError points at the offender otherwise.
    """
    with jax.named_scope("pcn.octree"):
        tree = oct.build(xyz, n_valid=n_valid)
    kw = {} if n_valid is None else {"n_valid": n_valid}
    try:
        with jax.named_scope("pcn.sample"):
            cidx = SAMPLERS.get(cfg.sampler)(
                xyz, tree=tree, n_centers=cfg.n_centers, key=key, **kw)
            centers = xyz[cidx]
    except TypeError as e:
        if kw and "n_valid" in str(e):
            raise TypeError(
                f"sampler {cfg.sampler!r} does not accept n_valid, which "
                f"the batched engine always passes; add n_valid=None to "
                f"its signature (see core.registry docstring)") from e
        raise
    try:
        with jax.named_scope("pcn.neighbors"):
            nbr = NEIGHBORS.get(cfg.neighbor)(
                xyz, centers, tree=tree, k=cfg.k, radius=cfg.radius,
                octree_level=cfg.octree_level, **kw)
    except TypeError as e:
        if kw and "n_valid" in str(e):
            raise TypeError(
                f"neighbor {cfg.neighbor!r} does not accept n_valid, "
                f"which the batched engine always passes; add "
                f"n_valid=None to its signature (see core.registry "
                f"docstring)") from e
        raise
    return cidx, nbr


def _center_vec(kind: str, centers_xyz, center_feats):
    """The per-subset vector the MLP input is normalized against."""
    return centers_xyz if kind == "sa" else center_feats


def _point_inputs(kind: str, xyz, feats, ids, center_vec):
    """MLP inputs for gathered point ids (..., ) against per-... center_vec.

    sa:   [xyz[ids] - c, feats[ids]]
    edge: [feats[ids] - c, c]
    """
    if kind == "sa":
        rel = xyz[ids] - center_vec
        return jnp.concatenate([rel, feats[ids]], axis=-1)
    rel = feats[ids] - center_vec
    return jnp.concatenate([rel, jnp.broadcast_to(center_vec, rel.shape)],
                           axis=-1)


def _subset_inputs(kind, xyz, feats, nbr_idx, centers_xyz, center_feats):
    """(S, K, f_in) MLP inputs for all subsets (dense/traditional path)."""
    cv = _center_vec(kind, centers_xyz, center_feats)
    return _point_inputs(kind, xyz, feats, nbr_idx, cv[:, None, :])


def _dense_reference(mlp: MLP, kind, xyz, feats, nbr_idx, centers_xyz,
                     center_feats=None, nbr_valid=None):
    """jnp oracle of the dense FC dataflow (kernels/gather_mlp).  Invalid
    neighbor slots are -BIG before the pool; fully-empty subsets pool to
    an all-zero row."""
    ids = nbr_idx if nbr_valid is None else jnp.where(nbr_valid, nbr_idx, 0)
    x = _subset_inputs(kind, xyz, feats, ids, centers_xyz, center_feats)
    y = apply_mlp(mlp, x)                                 # (S, K, Fout)
    if nbr_valid is None:
        return y.max(axis=1)                              # (S, Fout)
    pooled = jnp.where(nbr_valid[..., None], y, -BIG).max(axis=1)
    return jnp.where(nbr_valid.any(axis=1)[:, None], pooled, 0.0)


def _reuse_reference(mlp: MLP, pool_in, slot, comp, live=None):
    """jnp oracle of the reuse dataflow (kernels/hub_reuse): pool MLP,
    slot-gather, + comp, masked max over K.  -> (H, M, Fout), -BIG where a
    subset has no cached position.  ``live`` (H, M, K) further masks
    positions whose cache slot is not actually resident."""
    C = pool_in.shape[1]
    y = apply_mlp(mlp, pool_in)                           # (H, C, Fout)
    safe = jnp.clip(slot, 0, C - 1)
    g = jnp.take_along_axis(
        y, safe.reshape(y.shape[0], -1, 1), axis=1
    ).reshape(slot.shape + (y.shape[-1],))                # (H, M, K, Fout)
    g = g + comp[:, :, None, :]
    ok = slot >= 0 if live is None else (slot >= 0) & live
    g = jnp.where(ok[..., None], g, -BIG)
    return jnp.max(g, axis=2)


FC_BACKENDS.register("reference", FCBackend(
    name="reference", dense=_dense_reference, reuse=_reuse_reference))


def fc_traditional(mlp: MLP, xyz, feats, nbr_idx, centers_xyz,
                   center_feats=None, kind: str = "sa",
                   backend: FCBackend | None = None, nbr_valid=None):
    """Baseline FC: full MLP on all S*K gathered points, then max-pool.
    ``nbr_valid`` (S, K) bool masks ragged-batch -1 neighbor slots out of
    the pool (empty subsets become zero rows)."""
    backend = backend or FC_BACKENDS.get("reference")
    with jax.named_scope("pcn.dense_inputs"):
        pooled = backend.dense(mlp, kind, xyz, feats, nbr_idx, centers_xyz,
                               center_feats, nbr_valid)
        return post_pool_activation(mlp, pooled)


@jax.named_scope("pcn.reuse_inputs")
def _lpcn_reuse_inputs(mlp: MLP, xyz, feats, nbr_idx, centers_xyz,
                       islands: Islands, sched: Schedule, cfg: LPCNConfig,
                       center_feats=None):
    """Per-cloud jnp prep of the ``backend.reuse`` operands.

    Returns (pool_in (H, C, fin), comp (H, M, Fout), slot_live (H, M, K),
    sub_vec (H, M, Dc)); ``sub_vec`` is reused by the overflow/merge step.
    """
    S = nbr_idx.shape[0]
    H, M = islands.members.shape
    K = nbr_idx.shape[1]
    C = sched.pool_ids.shape[1]
    kind = cfg.block_kind

    cvec = _center_vec(kind, centers_xyz, center_feats)   # (S, Dc)
    hub_vec = cvec[islands.hub]                           # (H, Dc)

    # --- pool inputs (hub-relative), one eval per cached unique point ----
    pids = jnp.clip(sched.pool_ids, 0, xyz.shape[0] - 1)  # (H, C)
    pool_in = _point_inputs(kind, xyz, feats, pids, hub_vec[:, None, :])
    pool_live = sched.pool_ids >= 0

    # --- per-subset compensation (one Δ per non-hub subset) --------------
    mem = jnp.clip(islands.members, 0, S - 1)             # (H, M)
    sub_vec = cvec[mem]                                   # (H, M, Dc)
    delta = hub_vec[:, None, :] - sub_vec                 # (H, M, Dc)
    comp = compensation(mlp, delta, cfg.compensation, kind)  # (H, M, Fout)

    safe_slot = jnp.clip(sched.reuse_slot, 0, C - 1)
    slot_live = jnp.take_along_axis(
        pool_live, safe_slot.reshape(H, M * K), axis=1).reshape(H, M, K)
    return pool_in, comp, slot_live, sub_vec


@jax.named_scope("pcn.overflow")
def _lpcn_merge(mlp: MLP, xyz, feats, nbr_idx, islands: Islands,
                sched: Schedule, cfg: LPCNConfig, sub_vec, slot_live,
                reuse_pooled):
    """Overflow compute + max-merge with the reuse partials + scatter to
    center order.  Returns (out (S, Fout) *without* the dense fallback
    substituted, fb (S,) bool fallback rows)."""
    S, K = nbr_idx.shape
    H, M = islands.members.shape
    Fout = mlp.f_out
    kind = cfg.block_kind
    slot = sched.reuse_slot                               # (H, M, K)
    reuse_ok = (slot >= 0) & slot_live

    # --- compact overflow compute (never-cached positions) ---------------
    B = max(int(cfg.overflow_frac * M * K), K)            # overflow budget
    # only live positions (real subset row AND a valid gathered point)
    # are ever computed — ragged -1 slots stay out of the overflow queue
    need = (~reuse_ok) & sched.pos_live                   # (H, M, K)

    def island_overflow(need_h, ids_h, sub_vec_h):
        flatneed = need_h.reshape(-1)
        prio = jnp.where(flatneed, jnp.arange(M * K), M * K)
        takepos = jnp.argsort(prio)[:B]                   # overflow slots
        taken = flatneed[takepos]
        ids = ids_h.reshape(-1)[takepos]
        ids = jnp.clip(ids, 0, xyz.shape[0] - 1)
        row = jnp.clip(takepos // K, 0, M - 1)
        x = _point_inputs(kind, xyz, feats, ids, sub_vec_h[row])
        return takepos, taken, x

    mem = jnp.clip(islands.members, 0, S - 1)             # (H, M)
    ids_hmk = jnp.where(sched.pos_live, nbr_idx[mem], 0)
    takepos, taken, ox = jax.vmap(island_overflow)(
        need, ids_hmk, sub_vec)                           # (H,B),(H,B),(H,B,fin)
    o_out = apply_mlp(mlp, ox)                            # (H, B, Fout)

    # scatter overflow results into their own (H, M*K, Fout) canvas and
    # pool; max-pool commutes, so max(reuse_pooled, overflow_pooled) equals
    # pooling the combined position set
    over = jnp.full((H, M * K, Fout), -BIG, o_out.dtype)
    oidx = jnp.where(taken, takepos, M * K)               # drop untaken
    over = over.at[jnp.arange(H)[:, None], oidx].set(
        jnp.where(taken[..., None], o_out, -BIG), mode="drop")
    over_pooled = over.reshape(H, M, K, Fout).max(axis=2)
    pooled = jnp.maximum(reuse_pooled, over_pooled)       # (H, M, Fout)
    # merge-boundary guard: any subset both of whose sides stayed at the
    # -BIG merge identity zero-fills (mirrors gather_mlp's empty-subset
    # handling).  This subsumes the no-live-position case (empty ball
    # query on a nearly-empty ragged cloud) AND protects all-cached
    # subsets whose overflow side is empty against a reuse partial that
    # came back -BIG — the sentinel must never leak past the merge.
    pooled = jnp.where(pooled > -BIG / 2, pooled, 0.0)

    # rows whose overflow exceeded the budget fall back to the dense path
    covered = jnp.zeros((H, M * K), bool)
    covered = covered.at[jnp.arange(H)[:, None], oidx].set(taken, mode="drop")
    uncovered_row = (need.reshape(H, M * K) & ~covered
                     ).reshape(H, M, K).any(-1)           # (H, M)

    # --- scatter per-subset results to center order -----------------------
    out = jnp.zeros((S, Fout), pooled.dtype)
    rows_ok = sched.subset_valid
    tgt = jnp.where(rows_ok, islands.members, S)
    out = out.at[tgt.reshape(-1)].set(pooled.reshape(-1, Fout), mode="drop")

    # --- dense fallback rows: solo subsets + budget-exhausted rows --------
    fb = jnp.zeros((S,), bool).at[tgt.reshape(-1)].set(
        uncovered_row.reshape(-1), mode="drop") | islands.solo
    return out, fb


def fc_lpcn(mlp: MLP, xyz, feats, nbr_idx, centers_xyz,
            islands: Islands, sched: Schedule, cfg: LPCNConfig,
            center_feats=None, backend: FCBackend | None = None,
            nbr_valid=None):
    """Islandized FC: pool-MLP + compensated reuse + compact overflow.

    The two MXU-heavy dataflows — the dense path and the pool-MLP +
    reuse-gather — go through ``backend``; overflow/fallback bookkeeping
    is shared jnp.  Returns (S, Fout) center features — same contract as
    fc_traditional.  Ragged-batch slots (``sched.pos_live`` False) are
    neither reused nor computed; a subset with zero live positions pools
    to a zero row.
    """
    backend = backend or get_fc_backend(cfg.fc_backend)
    pool_in, comp, slot_live, sub_vec = _lpcn_reuse_inputs(
        mlp, xyz, feats, nbr_idx, centers_xyz, islands, sched, cfg,
        center_feats)
    with jax.named_scope("pcn.dense_inputs"):
        reuse_pooled = backend.reuse(mlp, pool_in, sched.reuse_slot, comp,
                                     slot_live)           # (H, M, Fout)
    out, fb = _lpcn_merge(mlp, xyz, feats, nbr_idx, islands, sched, cfg,
                          sub_vec, slot_live, reuse_pooled)
    with jax.named_scope("pcn.dense_inputs"):
        h_dense = backend.dense(mlp, cfg.block_kind, xyz, feats, nbr_idx,
                                centers_xyz, center_feats, nbr_valid)
        out = jnp.where(fb[:, None], h_dense, out)
        return post_pool_activation(mlp, out)


def fc_traditional_batched(mlp: MLP, xyz, feats, nbr_idx, centers_xyz,
                           center_feats=None, kind: str = "sa",
                           backend: FCBackend | None = None,
                           nbr_valid=None, kernel_kw=None, mesh=None):
    """Batched :func:`fc_traditional`: every array carries a leading (B,)
    axis; the MXU-heavy dense dataflow goes through the backend's batched
    entry point (ONE kernel dispatch for the whole cloud stack, or per
    data shard of ``mesh``)."""
    backend = backend or FC_BACKENDS.get("reference")
    with jax.named_scope("pcn.dense_inputs"):
        pooled = dense_batched(backend, mlp, kind, xyz, feats, nbr_idx,
                               centers_xyz, center_feats, nbr_valid,
                               kernel_kw, mesh)
        return post_pool_activation(mlp, pooled)


def fc_lpcn_batched(mlp: MLP, xyz, feats, nbr_idx, centers_xyz,
                    islands: Islands, sched: Schedule, cfg: LPCNConfig,
                    center_feats=None, backend: FCBackend | None = None,
                    nbr_valid=None, kernel_kw=None, mesh=None):
    """Batched :func:`fc_lpcn`: every array operand (including the
    ``islands`` / ``sched`` pytrees) carries a leading (B,) axis.

    The per-cloud jnp bookkeeping (reuse-operand prep, overflow compute,
    merge + scatter) is vmapped; the two MXU-heavy dataflows go through
    the backend's batched entry points so the whole cloud stack (or each
    data shard of ``mesh``) reaches the systolic array as ONE schedule
    per call site."""
    backend = backend or get_fc_backend(cfg.fc_backend)
    pool_in, comp, slot_live, sub_vec = jax.vmap(
        lambda x, f, n, c, isl, sch, cf: _lpcn_reuse_inputs(
            mlp, x, f, n, c, isl, sch, cfg, cf),
        in_axes=(0, 0, 0, 0, 0, 0, None if center_feats is None else 0),
    )(xyz, feats, nbr_idx, centers_xyz, islands, sched, center_feats)
    with jax.named_scope("pcn.dense_inputs"):
        reuse_pooled = reuse_batched(backend, mlp, pool_in,
                                     sched.reuse_slot, comp, slot_live,
                                     kernel_kw, mesh)
    out, fb = jax.vmap(
        lambda x, f, n, isl, sch, sv, sl, rp: _lpcn_merge(
            mlp, x, f, n, isl, sch, cfg, sv, sl, rp)
    )(xyz, feats, nbr_idx, islands, sched, sub_vec, slot_live, reuse_pooled)
    with jax.named_scope("pcn.dense_inputs"):
        h_dense = dense_batched(backend, mlp, cfg.block_kind, xyz, feats,
                                nbr_idx, centers_xyz, center_feats,
                                nbr_valid, kernel_kw, mesh)
        out = jnp.where(fb[..., None], h_dense, out)
        return post_pool_activation(mlp, out)


@dataclass
class BlockOutput:
    center_idx: jnp.ndarray
    center_xyz: jnp.ndarray
    features: jnp.ndarray
    islands: Islands | None
    schedule: Schedule | None
    nbr_idx: jnp.ndarray
    report: WorkloadReport | None = None
    center_valid: jnp.ndarray | None = None   # (S,) bool; None = all valid


@jax.tree_util.register_pytree_node_class
@dataclass
class BlockStructure:
    """Geometric stage of one building block: everything the FC stage
    needs that depends only on coordinates + RNG (never on features).

    Registered as a pytree so a vmapped structure pass can emit stacked
    (B, …) structures for the batched FC stage (``islands``/``schedule``
    are None in traditional mode; ``center_valid``/``nbr_valid`` are None
    when the cloud has no padding — both statically consistent across a
    batch).
    """
    center_idx: jnp.ndarray                   # (S,)
    center_xyz: jnp.ndarray                   # (S, 3)
    nbr: jnp.ndarray                          # (S, K)
    islands: Islands | None
    schedule: Schedule | None
    center_valid: jnp.ndarray | None          # (S,) bool
    nbr_valid: jnp.ndarray | None             # (S, K) bool

    def tree_flatten(self):
        return ((self.center_idx, self.center_xyz, self.nbr, self.islands,
                 self.schedule, self.center_valid, self.nbr_valid), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def structure_block(cfg: LPCNConfig, xyz: jnp.ndarray, key: jax.Array,
                    n_valid=None) -> BlockStructure:
    """Stage 1 of a building block: DS → octree → islandize → hub-schedule
    on ONE cloud.  Pure geometry — the emitted :class:`BlockStructure` is
    reusable across any feature tensor (and any FC backend)."""
    kds, kisl = jax.random.split(key)
    cidx, nbr = data_structuring(cfg, xyz, kds, n_valid=n_valid)
    centers_xyz = xyz[cidx]
    center_valid = None if n_valid is None else cidx < n_valid
    nbr_valid = None if n_valid is None else nbr >= 0
    if cfg.mode == "traditional":
        return BlockStructure(cidx, centers_xyz, nbr, None, None,
                              center_valid, nbr_valid)
    n_hubs = max(int(cidx.shape[0]) // cfg.island_size, 1)
    if center_valid is None:
        n_hubs_valid = None
    else:
        n_hubs_valid = jnp.maximum(
            center_valid.sum() // cfg.island_size, 1)
    with jax.named_scope("pcn.islandize"):
        isl = islandize(centers_xyz, n_hubs, level=cfg.octree_level,
                        capacity=cfg.island_capacity,
                        hub_select=cfg.hub_select, key=kisl,
                        center_valid=center_valid,
                        n_hubs_valid=n_hubs_valid)
    with jax.named_scope("pcn.schedule"):
        sched = build_schedule(isl, nbr, cfg.cache_capacity)
    return BlockStructure(cidx, centers_xyz, nbr, isl, sched,
                          center_valid, nbr_valid)


def compute_block_features(cfg: LPCNConfig, mlp: MLP, xyz, feats,
                           st: BlockStructure,
                           backend: FCBackend | None = None) -> jnp.ndarray:
    """Stage 2 of a building block: Feature Computation on ONE cloud over
    a pre-built :class:`BlockStructure`.  -> (S, Fout), padding centers
    zeroed."""
    backend = backend or get_fc_backend(cfg.fc_backend)
    with jax.named_scope("pcn.dense_inputs"):
        center_feats = feats[st.center_idx]
    if cfg.mode == "traditional":
        f = fc_traditional(mlp, xyz, feats, st.nbr, st.center_xyz,
                           center_feats, cfg.block_kind, backend=backend,
                           nbr_valid=st.nbr_valid)
    else:
        f = fc_lpcn(mlp, xyz, feats, st.nbr, st.center_xyz, st.islands,
                    st.schedule, cfg, center_feats, backend=backend,
                    nbr_valid=st.nbr_valid)
    if st.center_valid is not None:
        with jax.named_scope("pcn.dense_inputs"):
            f = jnp.where(st.center_valid[:, None], f, 0.0)
    return f


def compute_block_features_batched(cfg: LPCNConfig, mlp: MLP, xyz, feats,
                                   st: BlockStructure,
                                   backend: FCBackend | None = None,
                                   kernel_kw=None,
                                   mesh=None) -> jnp.ndarray:
    """Batched stage 2: ``st`` holds stacked (B, …) structures (a vmapped
    :func:`structure_block`), ``xyz``/``feats`` are (B, N, ·).  The MXU
    dataflows run through the backend's batched entry points — one kernel
    dispatch per call site for the whole cloud stack.

    ``mesh`` (None = single device) runs the FC dataflows per data shard
    and re-constrains the block's (B, S, Fout) output along the mesh
    data axes, so consecutive blocks of a mesh-sharded forward hand
    features over without a GSPMD replicate/reshard at the block
    boundary."""
    backend = backend or get_fc_backend(cfg.fc_backend)
    with jax.named_scope("pcn.dense_inputs"):
        center_feats = jnp.take_along_axis(
            feats, st.center_idx[..., None], axis=1)
    if cfg.mode == "traditional":
        f = fc_traditional_batched(mlp, xyz, feats, st.nbr, st.center_xyz,
                                   center_feats, cfg.block_kind,
                                   backend=backend,
                                   nbr_valid=st.nbr_valid,
                                   kernel_kw=kernel_kw, mesh=mesh)
    else:
        f = fc_lpcn_batched(mlp, xyz, feats, st.nbr, st.center_xyz,
                            st.islands, st.schedule, cfg, center_feats,
                            backend=backend, nbr_valid=st.nbr_valid,
                            kernel_kw=kernel_kw, mesh=mesh)
    if st.center_valid is not None:
        with jax.named_scope("pcn.dense_inputs"):
            f = jnp.where(st.center_valid[..., None], f, 0.0)
    if mesh is not None:
        from repro.dist.sharding import shard_leading
        f = shard_leading(f, mesh)
    return f


def lpcn_block(cfg: LPCNConfig, mlp: MLP, xyz: jnp.ndarray,
               feats: jnp.ndarray, key: jax.Array,
               with_report: bool = False, n_valid=None) -> BlockOutput:
    """One full building block on a single cloud (N,3)/(N,F) — the two
    stages (:func:`structure_block` + :func:`compute_block_features`)
    fused, the eager per-cloud entry point.

    ``n_valid`` (traced count or None) marks rows >= n_valid as padding.
    With it set, the block is numerically equivalent to running the
    unpadded (n_valid, ·) prefix: padding is never sampled, gathered,
    islandized, cached or pooled, its feature rows come back zeroed
    (``center_valid`` marks them), and the workload report counts only
    real work.
    """
    st = structure_block(cfg, xyz, key, n_valid=n_valid)
    backend = get_fc_backend(cfg.fc_backend)
    f = compute_block_features(cfg, mlp, xyz, feats, st, backend=backend)
    report = (analyze(st.islands, st.schedule, cfg.k)
              if with_report and st.islands is not None else None)
    return BlockOutput(st.center_idx, st.center_xyz, f, st.islands,
                       st.schedule, st.nbr, report,
                       center_valid=st.center_valid)
