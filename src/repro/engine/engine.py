"""The engine: one jit-able entry point from a cloud batch to logits.

Functional API (module-level, used with ``jax.jit``/``partial``):

    from functools import partial
    import jax
    from repro import engine
    from repro.models.pointnet2 import POINTNET2_C

    params = engine.init(jax.random.PRNGKey(0), POINTNET2_C)
    run = jax.jit(partial(engine.apply, spec=POINTNET2_C, mode="lpcn",
                          fc_backend="pallas"))
    logits = run(params, xyz_batch)          # (B, N, 3) -> (B, 40)

``spec``/``mode``/``fc_backend`` are static (closed over), so ONE compiled
executable serves every batch of the same shape — the serving path.  The
object API wraps the same functions with a cached jit per engine:

    eng = engine.PCNEngine(POINTNET2_C, mode="lpcn", fc_backend="pallas")
    params = eng.init(jax.random.PRNGKey(0))
    logits = eng.apply(params, batch)

The batched forward runs in two stages: the geometric chain (DS →
islandize → hub-schedule) is vmapped per cloud with per-cloud PRNG keys,
then Feature Computation runs *natively batched* — with the "pallas"
backend, one pallas_call per FC call site covers the whole cloud stack
(the batch is folded into the kernel grid).  ``kernel_kw`` tunes the
kernels' tile sizes / VMEM budget; the "pallas_vmap" backend keeps the
old vmap-of-kernels dispatch for A/B measurement.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import fc as _fc                     # noqa: F401  registers "pallas"
from .archs import EngineCtx, get_arch
from .params import Batch, PCNParams, as_batch, from_legacy
from .spec import PCNSpec


def init(key: jax.Array, spec: PCNSpec) -> PCNParams:
    """Initialize typed params for ``spec`` (arch-dispatched)."""
    return get_arch(spec).init(key, spec)


def apply_single(params, xyz, feats, key, *, spec: PCNSpec,
                 mode: str = "lpcn", fc_backend: str = "reference",
                 isl_kw: dict | None = None, with_report: bool = False,
                 n_valid=None):
    """One cloud (N, 3)/(N, F) -> (logits, WorkloadReport | None).

    cls: (n_classes,) logits.  seg: (N, n_classes) per-point logits.
    Accepts legacy param dicts as well as :class:`PCNParams`.

    ``n_valid`` (traced count or None) marks rows >= n_valid as padding:
    they are never sampled, gathered or pooled, and seg logits of padding
    rows come back zeroed — the output over the first ``n_valid`` rows
    equals running the unpadded (n_valid, ·) cloud.
    """
    params = from_legacy(params)
    ctx = EngineCtx.make(mode=mode, fc_backend=fc_backend, isl_kw=isl_kw,
                         with_report=with_report)
    return get_arch(spec).forward(params, spec, xyz, feats, key, ctx,
                                  n_valid=n_valid)


def apply(params, batch, *, spec: PCNSpec, mode: str = "lpcn",
          fc_backend: str = "reference", isl_kw: dict | None = None,
          kernel_kw: dict | None = None, mesh=None):
    """Padded batch -> logits, fully jit-compiled, batch-first.

    ``batch`` is a :class:`Batch` or a raw (B, N, 3) array.  Returns
    (B, n_classes) for cls specs, (B, N, n_classes) for seg specs.

    The forward runs in two stages: a per-cloud *vmapped* DS → octree →
    islandize → hub-schedule stage emits stacked (B, …) structures, then
    the FC stage presents the whole cloud stack to the backend's batched
    entry points — with ``fc_backend="pallas"`` that is ONE pallas_call
    per FC call site (grid ``(B, ⌈S/TS⌉)`` / ``(B, ⌈H/TH⌉)``), not one
    per cloud.  ``kernel_kw`` (static; e.g. ``{"ts": 32, "th": 2,
    "vmem_budget_mb": 8.0}``) overrides the kernels' VMEM-budget tile
    heuristic; backends without batched entries (``"reference"``,
    ``"pallas_vmap"``) fall back to vmap at the same seam.

    Ragged contract: ``batch.n_valid`` masks padding end to end, so
    ``apply(batch)[i]`` (cls) / ``apply(batch)[i, :n_valid[i]]`` (seg)
    equals :func:`apply_single` on cloud i's unpadded prefix; seg rows
    >= n_valid[i] are zeros.

    ``mesh`` (static; ``jax.sharding.Mesh`` with a ``"data"`` axis, e.g.
    from :func:`repro.launch.mesh.data_mesh`) turns on the sharded
    serving path: ``PCNParams`` are replicated (point MLPs are tiny),
    every batch-first (B, …) tensor — the :class:`Batch` leaves, the
    stacked structures between the two stages, each block's features and
    the logits — is constrained along the mesh's data axes, so the
    whole forward (including the Pallas ``(B, …)`` kernel grids) splits
    across devices.  ``mesh=None`` is the explicit no-mesh fast path:
    bit-identical numerics, and ``repro.dist`` is never even imported.
    """
    params = from_legacy(params)
    b = as_batch(batch)
    # build (and thereby validate kernel_kw + mesh) unconditionally, so a
    # typo'd knob raises even for archs that fall back to the vmap path
    ctx = EngineCtx.make(mode=mode, fc_backend=fc_backend,
                         isl_kw=isl_kw, kernel_kw=kernel_kw, mesh=mesh)
    arch = get_arch(spec)

    def run(params, b):
        if arch.forward_batched is not None:
            return arch.forward_batched(params, spec, b.xyz, b.feats,
                                        b.keys, ctx, b.n_valid)

        def one(xyz, feats, key, nv):
            logits, _ = apply_single(params, xyz, feats, key, spec=spec,
                                     mode=mode, fc_backend=fc_backend,
                                     isl_kw=isl_kw, with_report=False,
                                     n_valid=nv)
            return logits

        return jax.vmap(one)(b.xyz, b.feats, b.keys, b.n_valid)

    if ctx.mesh is None:          # no-mesh fast path
        return run(params, b)
    from repro.dist.sharding import replicate, shard_leading, use_mesh
    # the engine's own constraints pass ctx.mesh explicitly; use_mesh
    # additionally exposes the mesh to registry components and custom
    # FCBackends that call dist.sharding.constrain / active_mesh, the
    # same seam the LM side traces under
    with use_mesh(ctx.mesh):
        out = run(replicate(params, ctx.mesh), shard_leading(b, ctx.mesh))
        return shard_leading(out, ctx.mesh)


def apply_with_reports(params, batch, *, spec: PCNSpec, mode: str = "lpcn",
                       fc_backend: str = "reference",
                       isl_kw: dict | None = None):
    """Like :func:`apply` but also returns the stacked per-cloud
    :class:`WorkloadReport` (counter fields have a leading (B,) axis);
    None in traditional mode.  Padding rows contribute to no counter, so
    the (B,) counters are identical with and without padding."""
    params = from_legacy(params)
    b = as_batch(batch)

    def one(xyz, feats, key, nv):
        return apply_single(params, xyz, feats, key, spec=spec, mode=mode,
                            fc_backend=fc_backend, isl_kw=isl_kw,
                            with_report=(mode != "traditional"),
                            n_valid=nv)

    return jax.vmap(one)(b.xyz, b.feats, b.keys, b.n_valid)


class PCNEngine:
    """A spec bound to an execution configuration, with a cached jit.

    The engine object is the serving handle: construct once, ``init`` (or
    load) params, then ``apply`` on padded batches — recompilation happens
    only when the batch shape changes.  Inputs are normalized through
    :func:`as_batch` / :func:`from_legacy` *before* the cached jit, so
    alternating raw (B, N, 3) arrays, :class:`Batch` objects and legacy
    param dicts of the same shapes reuses one executable.

    ``mesh`` (optional) makes this a *sharded* serving handle: the cached
    jit closes over the mesh, batches are split along its data axes and
    params replicated (see :func:`apply`).  ``mesh=None`` keeps the
    single-device fast path (no ``repro.dist`` import, identical
    numerics).
    """

    def __init__(self, spec: PCNSpec, *, mode: str = "lpcn",
                 fc_backend: str = "reference",
                 isl_kw: dict | None = None,
                 kernel_kw: dict | None = None,
                 mesh=None):
        self.spec = spec
        self.mode = mode
        self.fc_backend = fc_backend
        self.isl_kw = dict(isl_kw or {})
        self.kernel_kw = dict(kernel_kw or {})
        self.mesh = mesh
        # validate the configuration eagerly (a bad mesh / typo'd knob
        # should fail at construction, not at the first traffic batch)
        EngineCtx.make(mode=mode, fc_backend=fc_backend, isl_kw=self.isl_kw,
                       kernel_kw=self.kernel_kw, mesh=mesh)
        isl_kw, kernel_kw = self.isl_kw, self.kernel_kw

        def pcn_step(params, batch):
            # a named function, so that the compiled program and every
            # op's name stack in a trace read ``jit(pcn_step)``
            return apply(params, batch, spec=spec, mode=mode,
                         fc_backend=fc_backend, isl_kw=isl_kw,
                         kernel_kw=kernel_kw, mesh=mesh)

        self._japply = jax.jit(pcn_step)

    def init(self, key: jax.Array) -> PCNParams:
        return init(key, self.spec)

    def apply(self, params, batch) -> jnp.ndarray:
        """Padded batch (Batch or (B, N, 3) array) -> logits."""
        return self._japply(from_legacy(params), as_batch(batch))

    @property
    def compile_count(self) -> int:
        """Number of distinct executables the cached jit has built — one
        per input *shape* ((B, N, F) bucket), since spec/mode/backend
        are static and ``n_valid`` is traced data.  The serving layer's
        compile-once-per-bucket contract is pinned against this."""
        return self._japply._cache_size()

    def bucket_callable(self, params, batch_size: int, n_points: int):
        """Compile (if not already cached) the executable for one
        (batch_size, n_points) bucket shape and return a callable
        ``batch -> logits`` bound to ``params`` — the serving layer's
        per-bucket seam.

        Compilation happens here, on a throwaway batch of the bucket's
        exact shape, so the first traffic batch of that shape hits the
        jit cache instead of absorbing the compile; calling this again
        for the same shape is a cache hit (``compile_count`` is
        unchanged).  Feature width comes from ``spec.in_feats``.
        """
        params = from_legacy(params)
        f = self.spec.in_feats
        rng = np.random.default_rng(0)
        xyz = jnp.asarray(rng.standard_normal((batch_size, n_points, 3)),
                          jnp.float32)
        feats = None if f <= 3 else jnp.concatenate(
            [xyz, jnp.zeros((batch_size, n_points, f - 3), jnp.float32)],
            -1)
        dummy = Batch.make(xyz, feats, key=jax.random.PRNGKey(0))
        self._japply(params, dummy).block_until_ready()
        japply = self._japply
        return lambda batch: japply(params, as_batch(batch))

    def apply_single(self, params, xyz, feats=None, key=None, *,
                     with_report: bool = False, n_valid=None):
        """Eager single-cloud path (keeps the legacy per-cloud contract)."""
        feats = xyz if feats is None else feats
        key = jax.random.PRNGKey(0) if key is None else key
        return apply_single(params, xyz, feats, key, spec=self.spec,
                            mode=self.mode, fc_backend=self.fc_backend,
                            isl_kw=self.isl_kw, with_report=with_report,
                            n_valid=n_valid)

    def __repr__(self):
        m = ("" if self.mesh is None
             else f", mesh={dict(self.mesh.shape)}")
        return (f"PCNEngine({self.spec.name}, mode={self.mode!r}, "
                f"fc_backend={self.fc_backend!r}{m})")
