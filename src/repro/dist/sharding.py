"""Sharding rules: logical axes, constraint helpers, sharding trees.

Model code never names mesh axes directly — it constrains activations
along *logical* axes which this module maps onto whatever mesh is
active:

  ``dp``    data parallel (batch rows)   -> every data-like mesh axis
                                            (``pod`` and ``data``)
  ``fsdp``  parameter sharding           -> ``data``
  ``tp``    tensor parallel              -> ``model``
  ``sp``    sequence parallel (between   -> ``model`` (Megatron-SP),
            blocks)                         off when ``use_mesh(sp=False)``

The mapping is held by the :func:`use_mesh` context.  Outside any
context every ``constrain`` is a no-op, so single-device code paths
(tests, the dev container) run unchanged — this is also the PCN
engine's "no mesh" fast path.

Two profiles: ``"tp"`` (the default 2-D data x model layout) and
``"flat_dp"`` (pure FSDP — ``tp``/``sp`` map to nothing; every matrix
is sharded over ``data`` only).

Divisibility: specs are filtered through :func:`fit_spec` — an axis
whose size does not divide the dimension is dropped (replicated) rather
than letting GSPMD pad.  Padding is usually fine, but padding
few-KV-head tensors onto a 16-way model axis provokes involuntary-remat
permutes; :func:`constrain_heads` is the explicit seam for that case.
"""
from __future__ import annotations

from contextlib import contextmanager
from contextvars import ContextVar

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# (mesh, {logical name -> physical axis or tuple or None}) of the
# innermost use_mesh context; None when no mesh is active.  A ContextVar
# (not a module global) so concurrent traces — two serve handles
# compiling under different meshes on different threads — each see their
# own context, like jax's own mesh context manager.
_ACTIVE: ContextVar[tuple | None] = ContextVar(
    "repro_dist_active_mesh", default=None)

_DATA_AXES = ("pod", "data")


def _mesh_sizes(mesh) -> dict:
    return dict(mesh.shape)


def _dp_axes(mesh):
    """All data-like axes present on ``mesh`` (batch rows shard over the
    product of pod x data)."""
    names = set(mesh.axis_names)
    axes = tuple(a for a in _DATA_AXES if a in names)
    return axes if len(axes) != 1 else axes[0]


def _physical(mesh, sp: bool = True, profile: str = "tp") -> dict:
    names = set(mesh.axis_names)
    model = "model" if "model" in names and profile != "flat_dp" else None
    return {
        "dp": _dp_axes(mesh) or None,
        "fsdp": "data" if "data" in names else None,
        "tp": model,
        "sp": model if sp else None,
    }


@contextmanager
def use_mesh(mesh, sp: bool = True, profile: str = "tp"):
    """Activate ``mesh`` for :func:`constrain` / :func:`constrain_heads`.

    ``sp`` gates Megatron-style sequence sharding between blocks
    (``ArchConfig.seq_shard_blocks``); ``profile`` selects the logical
    mapping (``ArchConfig.shard_profile``).  Nests and restores.
    """
    token = _ACTIVE.set((mesh, _physical(mesh, sp=sp, profile=profile)))
    try:
        yield mesh
    finally:
        _ACTIVE.reset(token)


def active_mesh():
    """The mesh of the innermost :func:`use_mesh` context (or None)."""
    active = _ACTIVE.get()
    return active[0] if active is not None else None


def fit_spec(spec: P, shape, mesh) -> P:
    """Drop spec entries whose mesh-axis product does not divide the
    dimension (replicate instead of letting GSPMD pad).  ``spec`` may be
    shorter than ``shape``; missing trailing dims are replicated."""
    sizes = _mesh_sizes(mesh)
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= sizes[a]
        out.append(entry if n and dim % n == 0 else None)
    return P(*out)


def _constrain_spec(x, spec, mesh):
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, fit_spec(spec, x.shape, mesh)))


def constrain(x, *logical):
    """Constrain ``x`` along logical axes (one name or None per dim).
    No-op outside a :func:`use_mesh` context."""
    active = _ACTIVE.get()
    if active is None:
        return x
    mesh, phys = active
    spec = P(*[phys.get(name) if name else None for name in logical])
    return _constrain_spec(x, spec, mesh)


def constrain_heads(x, n_heads: int):
    """Constrain a (B, S, H, Dh) tensor: batch over ``dp`` and heads over
    ``tp`` — but ONLY when the head count divides the model axis.  GSPMD
    pads 40 heads -> 48 fine, but padding few-KV-head tensors onto 16
    devices causes involuntary-remat permutes, so undersized head counts
    stay replicated on the head dim."""
    active = _ACTIVE.get()
    if active is None:
        return x
    mesh, phys = active
    tp = phys.get("tp")
    sizes = _mesh_sizes(mesh)
    heads = tp if tp is not None and n_heads % sizes[tp] == 0 else None
    spec = P(phys.get("dp"), None, heads, None)
    return _constrain_spec(x, spec, mesh)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

# column-parallel 2-D matrices (d_in, d_out): shard d_in over fsdp,
# d_out over tp (inputs replicated within a TP group, outputs split)
_COL = {"wq", "wk", "wv", "w_in", "w_gate", "w_x", "w_r", "w_i",
        "in_proj", "router", "lm_head"}
# row-parallel 2-D matrices (d_in, d_out): the contracted dim is the
# TP-split one (wo consumes TP-split head outputs)
_ROW = {"wo", "w_out", "out_proj"}


def param_spec(path: str, leaf, moe_shard: str = "ep") -> tuple:
    """Logical partition of one parameter leaf.

    ``path`` is the ``/``-joined pytree path (e.g. ``layers/0/mixer/wq``);
    ``leaf`` only needs ``.ndim``.  3-D leaves are stacked per-expert
    weights: ``moe_shard="ep"`` puts experts on the model axis (expert
    parallelism), ``"tp"`` shards inside each expert instead (grok: 8
    experts < 16-way model axis).
    """
    ndim = leaf.ndim
    if ndim == 0:
        return ()
    if ndim == 1:
        return (None,)
    name = path.rsplit("/", 1)[-1]
    if ndim == 3:  # (E, d_in, d_out) stacked expert weights
        if name in _ROW:
            return ("tp", None, "fsdp") if moe_shard == "ep" \
                else (None, "tp", "fsdp")
        return ("tp", "fsdp", None) if moe_shard == "ep" \
            else (None, "fsdp", "tp")
    if ndim == 2:
        if name == "embed":
            return ("tp", "fsdp")        # (V, D): vocab over model
        if name == "conv_w":
            return (None, "tp")          # depthwise conv: channels split
        if name in _ROW:
            return ("tp", "fsdp")
        if name in _COL:
            return ("fsdp", "tp")
        return ("fsdp", None)
    return (None,) * ndim


def _resolve(mesh):
    """The logical->physical mapping: the active context's if this mesh
    is the active one, else the default profile for ``mesh``."""
    active = _ACTIVE.get()
    if active is not None and active[0] is mesh:
        return active[1]
    return _physical(mesh)


def _named(mesh, spec, shape):
    return NamedSharding(mesh, fit_spec(spec, shape, mesh))


def param_shardings(params, mesh, moe_shard: str = "ep"):
    """NamedSharding tree for a parameter / optimizer-state tree.
    Leaves may be arrays or ShapeDtypeStructs (dry-run)."""
    phys = _resolve(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for kpath, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kpath)
        logical = param_spec(path, leaf, moe_shard)
        spec = P(*[phys.get(name) if name else None for name in logical])
        out.append(_named(mesh, spec, leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_shardings(batch, mesh):
    """NamedSharding tree for step inputs: leading (batch) dim over the
    data axes, everything else replicated.  Shared by the LM steps and
    the PCN engine's :class:`~repro.engine.params.Batch`."""
    dp = _dp_axes(mesh)

    def one(leaf):
        spec = P(dp) if leaf.ndim else P()
        return _named(mesh, spec, leaf.shape)

    return jax.tree.map(one, batch)


def cache_shardings(cache, mesh):
    """NamedSharding tree for decode caches: batch over ``dp``, the
    head/channel dim over ``tp`` where it divides (KV heads, SSD heads,
    conv/recurrent channels)."""
    phys = _resolve(mesh)
    dp, tp = phys.get("dp"), phys.get("tp")
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for kpath, leaf in flat:
        name = str(getattr(kpath[-1], "key", getattr(kpath[-1], "idx",
                                                     kpath[-1]))) \
            if kpath else ""
        nd = leaf.ndim
        if nd >= 4 and name in ("k", "v", "xk", "xv"):
            spec = P(dp, None, tp, None)       # (B, T, Hkv, Dh)
        elif nd == 3 and name in ("ks", "vs", "conv"):
            spec = P(dp, None, tp)             # (B, T, Hkv) / (B, W, C)
        elif name == "state":
            spec = P(dp, tp)                   # (B, H, ...) / (B, D)
        elif nd >= 1:
            spec = P(dp)
        else:
            spec = P()
        out.append(_named(mesh, spec, leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# PCN engine helpers (batch-first (B, ...) trees)
# ---------------------------------------------------------------------------

def shard_leading(tree, mesh=None):
    """Constrain every array leaf's leading dim over the data axes —
    the engine's sharding plan for stacked (B, ...) structures between
    forward stages.  ``mesh=None`` uses the active context (no-op when
    there is none)."""
    if mesh is None:
        mesh = active_mesh()
    if mesh is None:
        return tree
    dp = _dp_axes(mesh)

    def one(x):
        if getattr(x, "ndim", 0) == 0:
            return x
        return _constrain_spec(x, P(dp), mesh)

    return jax.tree.map(one, tree)


def replicate(tree, mesh):
    """Constrain every leaf fully replicated (the engine's PCNParams
    plan: point-MLP weights are tiny; every device holds them all)."""
    sh = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, sh), tree)


def on_data_shards(fn, mesh, data, replicated):
    """``fn(data, replicated)`` once per data shard (``shard_map``): every
    array leaf of ``data`` and the result split along its leading (B,)
    dim over the mesh's data axes, ``replicated`` whole on each device.

    The engine's batched FC entries go through here: Mosaic kernels
    cannot be partitioned automatically.  A batch that does not divide
    over the data axes runs unsplit (GSPMD decides, as for
    :func:`shard_leading`'s dropped specs)."""
    dp = _dp_axes(mesh)
    sizes = _mesh_sizes(mesh)
    n = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        n *= sizes[a]
    b = jax.tree.leaves(data)[0].shape[0]
    if b % n:
        return fn(data, replicated)
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(dp), P()),
                         out_specs=P(dp), check_vma=False)(data, replicated)
