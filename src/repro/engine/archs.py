"""Architecture families: typed init + single-cloud forward per family.

Each family is registered under the leading token of ``spec.name``
("pointnet2", "dgcnn", "pointnext", "pointvector"); unknown names fall
back to the generic SA-stack family.  Every gather/MLP block routes
through ``core.pipeline.lpcn_block`` — the Islandization Unit plugs into
each architecture uniformly (the paper's "seamlessly integrated" claim) —
and the FC backend, sampler and neighbor method are all registry-resolved.

Forwards operate on ONE cloud; ``engine.apply`` vmaps them over a padded
:class:`~repro.engine.params.Batch`.  The RNG key-split sequences mirror
the legacy ``repro.models`` code exactly, so the compatibility shims are
bit-identical to the old path.  Everything after the last block (global
pool, global MLP, head, a segmentation decoder) runs under the
``pcn.head`` scope, beside the stage scopes of ``core.pipeline``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.core.mlp import MLP, apply_mlp, init_mlp, post_pool_activation
from repro.core.pipeline import BIG as _BIG
from repro.core.pipeline import (LPCNConfig, compute_block_features_batched,
                                 lpcn_block, structure_block)
from repro.core.registry import Registry, get_fc_backend
from repro.core.workload import WorkloadReport

from .params import PCNParams
from .spec import BlockSpec, PCNSpec, arch_of, block_in_dim

ARCHS = Registry("arch")


@dataclass(frozen=True)
class Arch:
    """One architecture family: init(key, spec) -> PCNParams and
    forward(params, spec, xyz, feats, key, ctx, n_valid) ->
    (logits, report).  ``n_valid`` (traced count or None) marks rows
    >= n_valid of the cloud as padding; forwards must mask them out of
    sampling, pooling and per-point (seg) logits.

    ``forward_batched(params, spec, xyz, feats, keys, ctx, n_valid) ->
    logits`` (optional) is the batch-first two-stage forward the serving
    path uses: a vmapped per-cloud DS → octree → islandize → hub-schedule
    stage emits stacked (B, …) structures, then the FC stage runs through
    the backend's batched entry points — one kernel dispatch per FC call
    site for the whole cloud stack.  Families without it fall back to
    ``jax.vmap`` of ``forward``."""
    name: str
    init: callable
    forward: callable
    forward_batched: callable | None = None


@dataclass(frozen=True)
class EngineCtx:
    """Per-call static execution context (lifted out of the traced args).

    ``mesh`` is the engine-level sharding plan (None = the no-mesh fast
    path): when set, the batched forward keeps every stacked (B, …)
    tensor sharded along the mesh's data axes between stages —
    :class:`~repro.engine.params.Batch` leaves on the way in, the
    structure stacks between stage 1 and stage 2, and each block's
    feature tensor on the way out of the FC stage — so the two-stage
    forward splits across devices instead of letting GSPMD replicate at
    a stage boundary.  Params are replicated (point MLPs are tiny).
    """
    mode: str = "lpcn"
    fc_backend: str = "reference"
    isl_kw: tuple = ()            # sorted (key, value) pairs — hashable
    with_report: bool = False
    kernel_kw: tuple = ()         # sorted (key, value) pairs — hashable
    mesh: object = None           # jax.sharding.Mesh | None (hashable)

    KERNEL_KW_KEYS = frozenset({"ts", "th", "vmem_budget_mb", "lanes",
                                "dimension_semantics"})

    @staticmethod
    def make(mode="lpcn", fc_backend="reference", isl_kw=None,
             with_report=False, kernel_kw=None, mesh=None) -> "EngineCtx":
        kernel_kw = dict(kernel_kw or {})
        unknown = set(kernel_kw) - EngineCtx.KERNEL_KW_KEYS
        if unknown:
            raise ValueError(
                f"unknown kernel_kw key(s) {sorted(unknown)}; valid knobs: "
                f"{sorted(EngineCtx.KERNEL_KW_KEYS)} (a typo here would "
                f"silently fall back to the VMEM-budget heuristic)")
        sem = kernel_kw.get("dimension_semantics")
        if sem is not None:
            # JSON/CLI callers pass a list; the ctx must stay hashable and
            # the values must be real Mosaic semantics (K005 territory)
            sem = tuple(sem)
            if len(sem) != 2 or not set(sem) <= {"parallel", "arbitrary"}:
                raise ValueError(
                    f"dimension_semantics must be a pair drawn from "
                    f"('parallel', 'arbitrary'); got {sem!r}")
            kernel_kw["dimension_semantics"] = sem
        if mesh is not None and "data" not in mesh.axis_names:
            raise ValueError(
                f"engine meshes shard the batch along a 'data' axis; got "
                f"axes {tuple(mesh.axis_names)} (build one with "
                f"repro.launch.mesh.data_mesh / make_mesh)")
        return EngineCtx(mode=mode, fc_backend=fc_backend,
                         isl_kw=tuple(sorted((isl_kw or {}).items())),
                         with_report=with_report,
                         kernel_kw=tuple(sorted(kernel_kw.items())),
                         mesh=mesh)


def _maybe_shard(tree, ctx: EngineCtx):
    """Constrain stacked (B, …) leaves along the data axes of
    ``ctx.mesh`` (identity on the no-mesh fast path — repro.dist is not
    even imported)."""
    if ctx.mesh is None:
        return tree
    from repro.dist.sharding import shard_leading
    return shard_leading(tree, ctx.mesh)


def get_arch(spec: PCNSpec) -> Arch:
    name = arch_of(spec)
    return ARCHS.get(name if name in ARCHS else "pointnet2")


def block_cfg(b: BlockSpec, ctx: EngineCtx) -> LPCNConfig:
    return LPCNConfig(n_centers=b.n_centers, k=b.k, sampler=b.sampler,
                      neighbor=b.neighbor, radius=b.radius, mode=ctx.mode,
                      block_kind=b.kind, fc_backend=ctx.fc_backend,
                      **dict(ctx.isl_kw))


def _total(reports):
    if not reports:
        return None
    if len(reports) == 1:
        return reports[0]
    return WorkloadReport.sum_counters(reports)


def feature_propagation(xyz_dst, xyz_src, f_src, k: int = 3,
                        src_n_valid=None):
    """PointNet++ FP layer: inverse-distance 3-NN interpolation of source
    center features onto destination points (segmentation upsampling).
    ``src_n_valid`` masks padding source rows out of the 3-NN (their
    distance is pinned to +inf, so their weight is exactly zero)."""
    d = jnp.sum((xyz_dst[:, None, :] - xyz_src[None, :, :]) ** 2, -1)
    if src_n_valid is not None:
        src_ok = jnp.arange(xyz_src.shape[0])[None, :] < src_n_valid
        d = jnp.where(src_ok, d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    w = 1.0 / jnp.maximum(-neg, 1e-8)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-12)
    return (f_src[idx] * w[..., None]).sum(axis=1)


def _mask_rows(x, n_valid, fill=0.0):
    """Zero (or ``fill``) rows >= n_valid of a per-point array."""
    if n_valid is None:
        return x
    ok = jnp.arange(x.shape[0]) < n_valid
    return jnp.where(ok[:, None], x, fill)


def _mask_rows_b(x, n_valid, fill=0.0):
    """Batched :func:`_mask_rows`: zero rows >= n_valid[i] of x (B, N, F)."""
    if n_valid is None:
        return x
    ok = jnp.arange(x.shape[1])[None, :] < n_valid[:, None]
    return jnp.where(ok[..., None], x, fill)


def _structure_stack(spec: PCNSpec, ctx: EngineCtx, xyz, key, n_valid):
    """Stage 1 on ONE cloud: the geometric chain of the whole SA block
    stack (DS → octree → islandize → hub-schedule per block — coordinates
    and RNG only, no features).  The key-split sequence mirrors
    :func:`_run_blocks` exactly, so the batched forward is numerically
    identical to vmapping the fused per-cloud path.

    Returns (structures, nv_levels): one :class:`BlockStructure` per
    block and the per-level n_valid chain (downsampling samplers emit
    fully-valid center sets -> None below them; "all" keeps the count)."""
    structs = []
    cur_xyz, cur_nv = xyz, n_valid
    nv_levels = [n_valid]
    for b in spec.blocks:
        key, sub = jax.random.split(key)
        st = structure_block(block_cfg(b, ctx), cur_xyz, sub,
                             n_valid=cur_nv)
        structs.append(st)
        cur_xyz = st.center_xyz
        cur_nv = cur_nv if b.sampler == "all" else None
        nv_levels.append(cur_nv)
    return tuple(structs), tuple(nv_levels)


def _structure_stack_b(spec: PCNSpec, ctx: EngineCtx, xyz, keys, n_valid):
    """Vmapped :func:`_structure_stack`: emits stacked (B, …) structures
    for the batched FC stage."""
    return jax.vmap(
        lambda x, k, nv: _structure_stack(spec, ctx, x, k, nv)
    )(xyz, keys, n_valid)


def _compute_stack_b(params: PCNParams, spec: PCNSpec, ctx: EngineCtx,
                     xyz, feats, structs):
    """Batched stage 2 over an SA block stack: features flow through the
    backend's batched FC entry points block by block (each block's
    output re-constrained to the mesh data axes when ``ctx.mesh`` is
    set).  Returns (xyz_levels, final features)."""
    backend = get_fc_backend(ctx.fc_backend)
    kernel_kw = dict(ctx.kernel_kw)
    cur_xyz, cur_f = xyz, feats
    xyz_levels = [xyz]
    for b, mlp, st in zip(spec.blocks, params.blocks, structs):
        cur_f = compute_block_features_batched(
            block_cfg(b, ctx), mlp, cur_xyz, cur_f, st, backend=backend,
            kernel_kw=kernel_kw, mesh=ctx.mesh)
        cur_xyz = st.center_xyz
        xyz_levels.append(cur_xyz)
    return xyz_levels, cur_f


def _fp_b(xyz_dst, xyz_src, f_src, src_n_valid=None, k: int = 3):
    """Vmapped :func:`feature_propagation` (seg decoder level)."""
    return jax.vmap(
        lambda d, s, f, nv: feature_propagation(d, s, f, k=k,
                                                src_n_valid=nv),
        in_axes=(0, 0, 0, None if src_n_valid is None else 0),
    )(xyz_dst, xyz_src, f_src, src_n_valid)


def _run_blocks(params: PCNParams, spec: PCNSpec, xyz, feats, key,
                ctx: EngineCtx, n_valid=None):
    """SA block stack on one cloud -> (cx, cf, reports, saved).

    ``n_valid`` masks the first block's input padding.  Downsampling
    samplers pick only valid points, so deeper blocks see fully-valid
    center sets; the "all" sampler keeps every row, so padding (and its
    count) propagates unchanged.
    """
    reports, saved = [], []
    cur_xyz, cur_f = xyz, feats
    cur_nv = n_valid
    nv_levels = [n_valid]
    for b, mlp in zip(spec.blocks, params.blocks):
        key, sub = jax.random.split(key)
        out = lpcn_block(block_cfg(b, ctx), mlp, cur_xyz, cur_f, sub,
                         with_report=ctx.with_report, n_valid=cur_nv)
        saved.append((cur_xyz, cur_f, out))
        cur_xyz, cur_f = out.center_xyz, out.features
        cur_nv = cur_nv if b.sampler == "all" else None
        nv_levels.append(cur_nv)
        if ctx.with_report and out.report is not None:
            reports.append(out.report)
    return cur_xyz, cur_f, reports, saved, nv_levels


def _global_pool(params: PCNParams, center_xyz, center_f, n_valid=None):
    """Final global SA: one subset containing every remaining center —
    the paper's example of a no-overlap layer (processed traditionally).
    ``n_valid`` masks padding centers (possible when every block uses the
    "all" sampler) out of the centroid and the global max."""
    if params.global_mlp is None:
        return _mask_rows(center_f, n_valid, fill=-_BIG).max(axis=0)
    if n_valid is None:
        centroid = center_xyz.mean(axis=0)
    else:
        ok = (jnp.arange(center_xyz.shape[0]) < n_valid)[:, None]
        centroid = jnp.where(ok, center_xyz, 0.0).sum(axis=0) \
            / jnp.maximum(n_valid, 1)
    x = jnp.concatenate([center_xyz - centroid, center_f], axis=-1)
    return _mask_rows(apply_mlp(params.global_mlp, x), n_valid,
                      fill=-_BIG).max(axis=0)


# ---- generic SA stack (PointNet++ and ad-hoc specs) -------------------------

def _init_pointnet2(key, spec: PCNSpec) -> PCNParams:
    blocks = []
    f = spec.in_feats
    for b in spec.blocks:
        key, sub = jax.random.split(key)
        dims = [block_in_dim(b.kind, f), *b.mlp_dims]
        blocks.append(init_mlp(sub, dims, spec.activation))
        f = b.mlp_dims[-1]
    global_mlp = None
    if spec.task == "cls":
        key, sub = jax.random.split(key)
        if spec.global_mlp:
            global_mlp = init_mlp(sub, [3 + f, *spec.global_mlp],
                                  spec.activation)
            f = spec.global_mlp[-1]
    key, sub = jax.random.split(key)
    head = init_mlp(sub, [f, *spec.head_dims, spec.n_classes], "per_layer")
    return PCNParams(blocks=tuple(blocks), head=head, global_mlp=global_mlp)


def _fwd_pointnet2(params: PCNParams, spec: PCNSpec, xyz, feats, key,
                   ctx: EngineCtx, n_valid=None):
    cx, cf, reports, saved, nv_levels = _run_blocks(params, spec, xyz,
                                                    feats, key, ctx, n_valid)
    with jax.named_scope("pcn.head"):
        if spec.task == "cls":
            g = _global_pool(params, cx, cf, n_valid=nv_levels[-1])
            return apply_mlp(params.head, g), _total(reports)
        # segmentation: FP decoder back up the saved pyramid
        f = cf
        xyz_levels = [s[0] for s in saved] + [cx]
        for lvl in range(len(saved) - 1, -1, -1):
            f = feature_propagation(xyz_levels[lvl], xyz_levels[lvl + 1], f,
                                    src_n_valid=nv_levels[lvl + 1])
        # per-point logits of padding rows are zeroed (ragged contract)
        return (_mask_rows(apply_mlp(params.head, f), n_valid),
                _total(reports))


def _fwd_pointnet2_batched(params: PCNParams, spec: PCNSpec, xyz, feats,
                           keys, ctx: EngineCtx, n_valid=None):
    """Two-stage batched forward: vmapped geometry stack, then batched FC
    + head.  Numerically identical to vmapping :func:`_fwd_pointnet2`."""
    structs, nv_levels = _maybe_shard(
        _structure_stack_b(spec, ctx, xyz, keys, n_valid), ctx)
    xyz_levels, cf = _compute_stack_b(params, spec, ctx, xyz, feats,
                                      structs)
    with jax.named_scope("pcn.head"):
        if spec.task == "cls":
            nv = nv_levels[-1]
            g = jax.vmap(
                lambda c, f, v: _global_pool(params, c, f, n_valid=v),
                in_axes=(0, 0, None if nv is None else 0),
            )(xyz_levels[-1], cf, nv)
            return apply_mlp(params.head, g)
        f = cf
        for lvl in range(len(spec.blocks) - 1, -1, -1):
            f = _fp_b(xyz_levels[lvl], xyz_levels[lvl + 1], f,
                      nv_levels[lvl + 1])
        return _mask_rows_b(apply_mlp(params.head, f), n_valid)


ARCHS.register("pointnet2", Arch("pointnet2", _init_pointnet2,
                                 _fwd_pointnet2,
                                 _fwd_pointnet2_batched))


# ---- DGCNN (EdgeConv; every point a center) ---------------------------------

def _init_dgcnn(key, spec: PCNSpec) -> PCNParams:
    """The EdgeConv layers, then the head: over the max-pooled per-point
    embedding ``global_mlp`` of their concatenation (cls), or over each
    point's concatenation beside its broadcast max (seg)."""
    keys = jax.random.split(key, len(spec.blocks) + 2)
    blocks, f = [], spec.in_feats
    for sub, b in zip(keys, spec.blocks):
        blocks.append(init_mlp(sub, [block_in_dim(b.kind, f), *b.mlp_dims],
                               spec.activation))
        f = b.mlp_dims[-1]
    cat_dim = sum(b.mlp_dims[-1] for b in spec.blocks)
    global_mlp, head_in = None, 2 * cat_dim
    if spec.task == "cls":
        if not spec.global_mlp:
            raise ValueError(f"{spec.name}: DGCNN classification needs its "
                             "per-point embedding (global_mlp)")
        global_mlp = init_mlp(keys[-2], [cat_dim, *spec.global_mlp],
                              spec.activation)
        head_in = spec.global_mlp[-1]
    head = init_mlp(keys[-1], [head_in, *spec.head_dims, spec.n_classes],
                    "per_layer")
    return PCNParams(blocks=tuple(blocks), head=head, global_mlp=global_mlp)


def _dgcnn_pool(params: PCNParams, cat, n_valid):
    """The classifier's global feature of one cloud (``pcn.embed``): each
    point's embedding of the concatenated EdgeConv outputs, its max over
    valid points, then the embedding's activation.  cat (N, F) -> (G,)."""
    with jax.named_scope("pcn.embed"):
        e = apply_mlp(params.global_mlp, cat)
        g = _mask_rows(e, n_valid, fill=-_BIG).max(axis=0)
        return post_pool_activation(params.global_mlp, g)


def _fwd_dgcnn(params: PCNParams, spec: PCNSpec, xyz, feats, key,
               ctx: EngineCtx, n_valid=None):
    """EdgeConv stack; every layer keeps all N points (no downsampling).
    Padding rows stay in every layer (static shapes) but are excluded
    from neighbor sets, islands and the global max-pool."""
    reports, per_layer = [], []
    f = feats
    for b, mlp in zip(spec.blocks, params.blocks):
        key, sub = jax.random.split(key)
        out = lpcn_block(block_cfg(b, ctx), mlp, xyz, f, sub,
                         with_report=ctx.with_report, n_valid=n_valid)
        f = out.features
        per_layer.append(f)
        if ctx.with_report and out.report is not None:
            reports.append(out.report)
    with jax.named_scope("pcn.head"):
        cat = jnp.concatenate(per_layer, axis=-1)
    if spec.task == "cls":
        g = _dgcnn_pool(params, cat, n_valid)
        with jax.named_scope("pcn.head"):
            return apply_mlp(params.head, g), _total(reports)
    with jax.named_scope("pcn.head"):
        gmax = _mask_rows(cat, n_valid, fill=-_BIG).max(axis=0)
        per_point = jnp.concatenate(
            [cat, jnp.broadcast_to(gmax[None], cat.shape[:1] + gmax.shape)],
            axis=-1)
        return _mask_rows(apply_mlp(params.head, per_point), n_valid), \
            _total(reports)


def _structure_dgcnn(spec: PCNSpec, ctx: EngineCtx, xyz, key, n_valid):
    """Stage 1 on ONE cloud for the EdgeConv stack: every block structures
    the SAME cloud (no downsampling); key splits mirror
    :func:`_fwd_dgcnn`."""
    structs = []
    for b in spec.blocks:
        key, sub = jax.random.split(key)
        structs.append(structure_block(block_cfg(b, ctx), xyz, sub,
                                       n_valid=n_valid))
    return tuple(structs)


def _fwd_dgcnn_batched(params: PCNParams, spec: PCNSpec, xyz, feats, keys,
                       ctx: EngineCtx, n_valid=None):
    """Two-stage batched EdgeConv forward (see :func:`_fwd_dgcnn`)."""
    structs = _maybe_shard(jax.vmap(
        lambda x, k, nv: _structure_dgcnn(spec, ctx, x, k, nv)
    )(xyz, keys, n_valid), ctx)
    backend = get_fc_backend(ctx.fc_backend)
    kernel_kw = dict(ctx.kernel_kw)
    f, per_layer = feats, []
    for b, mlp, st in zip(spec.blocks, params.blocks, structs):
        f = compute_block_features_batched(block_cfg(b, ctx), mlp, xyz, f,
                                           st, backend=backend,
                                           kernel_kw=kernel_kw,
                                           mesh=ctx.mesh)
        per_layer.append(f)
    with jax.named_scope("pcn.head"):
        cat = jnp.concatenate(per_layer, axis=-1)
    if spec.task == "cls":
        g = jax.vmap(lambda c, v: _dgcnn_pool(params, c, v),
                     in_axes=(0, None if n_valid is None else 0))(cat, n_valid)
        with jax.named_scope("pcn.head"):
            return apply_mlp(params.head, g)
    with jax.named_scope("pcn.head"):
        gmax = _mask_rows_b(cat, n_valid, fill=-_BIG).max(axis=1)
        per_point = jnp.concatenate(
            [cat, jnp.broadcast_to(gmax[:, None],
                                   cat.shape[:2] + gmax.shape[-1:])], axis=-1)
        return _mask_rows_b(apply_mlp(params.head, per_point), n_valid)


ARCHS.register("dgcnn", Arch("dgcnn", _init_dgcnn, _fwd_dgcnn,
                             _fwd_dgcnn_batched))


# ---- PointNeXt (stem + SA stages with InvResMLP residuals) ------------------

def _init_pointnext(key, spec: PCNSpec, stem_dim: int = 32) -> PCNParams:
    key, sub = jax.random.split(key)
    stem = init_mlp(sub, [spec.in_feats, stem_dim], "per_layer")
    blocks, extras = [], []
    f = stem_dim
    for b in spec.blocks:
        key, s1, s2 = jax.random.split(key, 3)
        blocks.append(init_mlp(s1, [3 + f, *b.mlp_dims], spec.activation))
        f = b.mlp_dims[-1]
        # InvResMLP: pointwise expansion x4 + projection, residual
        extras.append(init_mlp(s2, [f, 4 * f, f], "per_layer"))
    key, sub = jax.random.split(key)
    head = init_mlp(sub, [f, *spec.head_dims, spec.n_classes], "per_layer")
    return PCNParams(blocks=tuple(blocks), head=head, stem=stem,
                     extras=tuple(extras))


def _fwd_stem_stack(params, spec, xyz, feats, key, ctx, combine,
                    n_valid=None):
    """Shared stem + SA stack + FP decoder used by PointNeXt/PointVector;
    ``combine(extra_mlp, block_features)`` is the per-stage residual."""
    reports = []
    f = apply_mlp(params.stem, feats)
    cur_xyz = xyz
    cur_nv = n_valid
    xyz_levels = [xyz]
    nv_levels = [n_valid]
    for b, mlp, extra in zip(spec.blocks, params.blocks, params.extras):
        key, sub = jax.random.split(key)
        out = lpcn_block(block_cfg(b, ctx), mlp, cur_xyz, f, sub,
                         with_report=ctx.with_report, n_valid=cur_nv)
        f = combine(extra, out.features)
        cur_xyz = out.center_xyz
        cur_nv = cur_nv if b.sampler == "all" else None
        xyz_levels.append(cur_xyz)
        nv_levels.append(cur_nv)
        if ctx.with_report and out.report is not None:
            reports.append(out.report)
    with jax.named_scope("pcn.head"):
        for lvl in range(len(spec.blocks) - 1, -1, -1):
            f = feature_propagation(xyz_levels[lvl], xyz_levels[lvl + 1], f,
                                    src_n_valid=nv_levels[lvl + 1])
        # per-point logits of padding rows are zeroed (ragged contract)
        return (_mask_rows(apply_mlp(params.head, f), n_valid),
                _total(reports))


def _fwd_stem_stack_batched(params, spec, xyz, feats, keys, ctx, combine,
                            n_valid=None):
    """Two-stage batched :func:`_fwd_stem_stack` (PointNeXt/PointVector):
    vmapped geometry stack, batched stem/FC/residuals, vmapped FP
    decoder."""
    structs, nv_levels = _maybe_shard(
        _structure_stack_b(spec, ctx, xyz, keys, n_valid), ctx)
    backend = get_fc_backend(ctx.fc_backend)
    kernel_kw = dict(ctx.kernel_kw)
    f = apply_mlp(params.stem, feats)
    cur_xyz = xyz
    xyz_levels = [xyz]
    for b, mlp, extra, st in zip(spec.blocks, params.blocks, params.extras,
                                 structs):
        h = compute_block_features_batched(block_cfg(b, ctx), mlp, cur_xyz,
                                           f, st, backend=backend,
                                           kernel_kw=kernel_kw,
                                           mesh=ctx.mesh)
        f = combine(extra, h)
        cur_xyz = st.center_xyz
        xyz_levels.append(cur_xyz)
    with jax.named_scope("pcn.head"):
        for lvl in range(len(spec.blocks) - 1, -1, -1):
            f = _fp_b(xyz_levels[lvl], xyz_levels[lvl + 1], f,
                      nv_levels[lvl + 1])
        return _mask_rows_b(apply_mlp(params.head, f), n_valid)


def _fwd_pointnext(params, spec, xyz, feats, key, ctx, n_valid=None):
    return _fwd_stem_stack(params, spec, xyz, feats, key, ctx,
                           lambda inv, h: h + apply_mlp(inv, h),
                           n_valid=n_valid)


def _fwd_pointnext_batched(params, spec, xyz, feats, keys, ctx,
                           n_valid=None):
    return _fwd_stem_stack_batched(params, spec, xyz, feats, keys, ctx,
                                   lambda inv, h: h + apply_mlp(inv, h),
                                   n_valid=n_valid)


ARCHS.register("pointnext", Arch("pointnext", _init_pointnext,
                                 _fwd_pointnext,
                                 _fwd_pointnext_batched))


# ---- PointVector (stem + SA stages with vector recombination) ---------------

def _init_pointvector(key, spec: PCNSpec, stem_dim: int = 64) -> PCNParams:
    key, sub = jax.random.split(key)
    stem = init_mlp(sub, [spec.in_feats, stem_dim], "per_layer")
    blocks, extras = [], []
    f = stem_dim
    for b in spec.blocks:
        key, s1, s2 = jax.random.split(key, 3)
        blocks.append(init_mlp(s1, [3 + f, *b.mlp_dims], spec.activation))
        f = b.mlp_dims[-1]
        # vector branch: per-center linear recombination post-pooling
        extras.append(init_mlp(s2, [f, f], "per_layer"))
    key, sub = jax.random.split(key)
    head = init_mlp(sub, [f, *spec.head_dims, spec.n_classes], "per_layer")
    return PCNParams(blocks=tuple(blocks), head=head, stem=stem,
                     extras=tuple(extras))


def _fwd_pointvector(params, spec, xyz, feats, key, ctx, n_valid=None):
    return _fwd_stem_stack(params, spec, xyz, feats, key, ctx,
                           lambda vec, h: jax.nn.relu(apply_mlp(vec, h)),
                           n_valid=n_valid)


def _fwd_pointvector_batched(params, spec, xyz, feats, keys, ctx,
                             n_valid=None):
    return _fwd_stem_stack_batched(
        params, spec, xyz, feats, keys, ctx,
        lambda vec, h: jax.nn.relu(apply_mlp(vec, h)), n_valid=n_valid)


ARCHS.register("pointvector", Arch("pointvector", _init_pointvector,
                                   _fwd_pointvector,
                                   _fwd_pointvector_batched))
