"""Typed, pytree-registered containers for the engine API.

:class:`PCNParams` replaces the per-model ``{"blocks": [...], ...}`` dicts:
one frozen dataclass covering every architecture family (SA stacks, DGCNN,
PointNeXt, PointVector), registered as a JAX pytree so whole-model params
flow through ``jit`` / ``vmap`` / ``grad`` / optimizers untouched.

:class:`Batch` is the batched input container: padded (B, N, 3) clouds with
per-cloud features, PRNG keys and valid-point counts.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mlp import MLP


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class PCNParams:
    """All parameters of one PCN.

    blocks:      one MLP per building block (the FC-step point MLPs).
    head:        classifier / per-point head MLP.
    global_mlp:  final global-SA MLP, or DGCNN's per-point embedding
                 before its global pool (cls models; None otherwise).
    stem:        per-point input embedding (PointNeXt/PointVector; None
                 otherwise).
    extras:      per-block side branches — InvResMLP (PointNeXt) or the
                 vector-recombination MLPs (PointVector); empty otherwise.
    """
    blocks: tuple
    head: MLP
    global_mlp: MLP | None = None
    stem: MLP | None = None
    extras: tuple = ()

    def tree_flatten(self):
        return ((self.blocks, self.head, self.global_mlp, self.stem,
                 self.extras), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def from_legacy(params) -> PCNParams:
    """Convert a legacy per-model param dict to :class:`PCNParams`.

    Accepts the three historical dict layouts ({"blocks","global","head"},
    {"stem","blocks","invres","head"}, {"stem","blocks","vector","head"});
    a PCNParams passes through unchanged.
    """
    if isinstance(params, PCNParams):
        return params
    extras = params.get("invres") or params.get("vector") or ()
    return PCNParams(
        blocks=tuple(params["blocks"]),
        head=params["head"],
        global_mlp=params.get("global"),
        stem=params.get("stem"),
        extras=tuple(extras),
    )


def to_legacy(params: PCNParams, arch: str) -> dict:
    """Render :class:`PCNParams` in the legacy dict layout of ``arch``
    (for old call sites that index ``params["blocks"]`` etc.)."""
    if arch == "pointnext":
        return {"stem": params.stem, "blocks": list(params.blocks),
                "invres": list(params.extras), "head": params.head}
    if arch == "pointvector":
        return {"stem": params.stem, "blocks": list(params.blocks),
                "vector": list(params.extras), "head": params.head}
    return {"blocks": list(params.blocks), "global": params.global_mlp,
            "head": params.head}


def validate_cloud(arr, name: str = "xyz", index=None):
    """Host-side payload validation shared by :meth:`Batch.make`'s /
    :meth:`Batch.from_clouds`'s ``validate=`` path and the serving
    admission guard: reject non-finite values, coerce any floating
    dtype to float32, refuse non-floating dtypes.  Returns the cloud
    as a float32 numpy array.

    Validation is eager-only (it inspects values, which a traced array
    cannot do) — run it where the data is still host-side, *before*
    jit: a NaN that reaches a compiled kernel silently corrupts every
    reduction that touches it, with no error to catch.
    """
    tag = name if index is None else f"{name}[{index}]"
    a = np.asarray(arr)
    if not np.issubdtype(a.dtype, np.floating):
        raise ValueError(
            f"{tag} has dtype {a.dtype}, which is not a floating point "
            f"cloud payload; convert to float32 before submitting")
    if a.dtype != np.float32:
        a = a.astype(np.float32)     # f64/f16 inputs: coerce, don't trust
                                     # implicit x64 downcasts
    if not np.isfinite(a).all():
        n_bad = int(np.size(a) - np.isfinite(a).sum())
        rows = np.unique(np.argwhere(~np.isfinite(a))[:, 0])[:4]
        raise ValueError(
            f"{tag} contains {n_bad} non-finite value(s) (NaN/Inf), e.g. "
            f"in row(s) {rows.tolist()}; refuse or clean the cloud before "
            f"it reaches a compiled kernel")
    return a


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class Batch:
    """A padded batch of point clouds.

    xyz:     (B, N, 3) coordinates; clouds shorter than N are padded by
             repeating their last point (any finite padding works — it is
             fully masked; repeat-last keeps values well-conditioned).
    feats:   (B, N, F) per-point input features (xyz for plain geometry).
    keys:    (B, 2) uint32 — one PRNG key per cloud (drives random
             sampling / hub selection independently per cloud).  Typed
             keys are canonicalized to raw uint32 key data by ``make`` so
             the pytree signature (and the engine's jit cache) is stable.
    n_valid: (B,) int32 — true point count per cloud before padding.

    Ragged contract (enforced end to end by the engine): rows >= n_valid
    are padding — never sampled as centers, never returned by neighbor
    search, never cached/pooled/islandized, excluded from every
    WorkloadReport counter, and their per-point (seg) logits are zeroed.
    ``engine.apply(batch)[i]`` equals ``engine.apply_single`` on cloud
    i's unpadded prefix (rows [:n_valid[i]] for seg outputs).
    """
    xyz: jnp.ndarray
    feats: jnp.ndarray
    keys: jnp.ndarray
    n_valid: jnp.ndarray

    def tree_flatten(self):
        return ((self.xyz, self.feats, self.keys, self.n_valid), ())

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def batch_size(self) -> int:
        return self.xyz.shape[0]

    @staticmethod
    def make(xyz, feats=None, key=None, n_valid=None, *,
             validate: bool = False) -> "Batch":
        """Wrap pre-stacked (B, N, 3)/(B, N, F) arrays.  ``key`` may be a
        single PRNG key (split per cloud) or (B, 2) per-cloud keys.

        ``validate=True`` runs the host-side payload check
        (:func:`validate_cloud`): non-finite values are rejected with
        an actionable error and floating dtypes are coerced to float32
        — eager inputs only (traced arrays cannot be value-checked)."""
        if validate:
            xyz = validate_cloud(xyz, "xyz")
            if feats is not None:
                feats = validate_cloud(feats, "feats")
        xyz = jnp.asarray(xyz)
        b, n = xyz.shape[0], xyz.shape[1]
        feats = xyz if feats is None else jnp.asarray(feats)
        if key is None:
            key = jax.random.PRNGKey(0)
        # a single key is ndim-1 raw uint32 or ndim-0 typed; anything with
        # one more axis is already per-cloud
        typed = jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key)
        single = key.ndim == (0 if typed else 1)
        keys = jax.random.split(key, b) if single else key
        if jax.dtypes.issubdtype(keys.dtype, jax.dtypes.prng_key):
            # canonicalize typed keys -> raw uint32 so a Batch always has
            # the same pytree signature (no retrace vs raw-array callers)
            keys = jax.random.key_data(keys)
        # host numpy keys (the serving dispatcher stacks per-request key
        # data) must become jax Arrays too — the jit cache distinguishes
        # ndarray leaves from ArrayImpl leaves, which would force one
        # spurious recompile per bucket
        keys = jnp.asarray(keys)
        if n_valid is None:
            n_valid = jnp.full((b,), n, jnp.int32)
        return Batch(xyz=xyz, feats=feats, keys=keys,
                     n_valid=jnp.asarray(n_valid, jnp.int32))

    @staticmethod
    def from_clouds(clouds, feats=None, key=None, n_pad=None, *,
                    validate: bool = False) -> "Batch":
        """Stack variable-size clouds into one padded batch.

        Each cloud is padded to ``n_pad`` rows (default: the longest
        cloud) by repeating its last point; ``n_valid`` records the true
        sizes.  A cloud already at ``n_pad`` passes through untouched,
        and an *empty* (0, ·) cloud — the serving dispatcher's
        batch-fill rows for partial batches — is zero-filled and fully
        masked via ``n_valid == 0``.  Raises if ``n_pad`` is shorter
        than the longest cloud (silent truncation would break the
        ragged contract).

        ``validate=True`` runs :func:`validate_cloud` per cloud (and
        per feature array): NaN/Inf rejected with the offending cloud
        index in the message, dtypes coerced to float32 — the serving
        admission guard runs the same check per request at ``submit``.
        """
        clouds = [np.asarray(c) for c in clouds]
        if not clouds:
            raise ValueError("from_clouds needs at least one cloud")
        if validate:
            clouds = [validate_cloud(c, "clouds", i)
                      for i, c in enumerate(clouds)]
            if feats is not None:
                feats = [validate_cloud(f, "feats", i)
                         for i, f in enumerate(feats)]
        longest = max(c.shape[0] for c in clouds)
        n = longest if n_pad is None else int(n_pad)
        if n < longest:
            raise ValueError(
                f"n_pad={n} is shorter than the longest cloud "
                f"({longest} points); pick a bucket that fits")
        if n < 1:
            raise ValueError(
                "all clouds are empty; pass n_pad >= 1 to fix the "
                "padded shape")
        n_valid = np.array([c.shape[0] for c in clouds], np.int32)

        def pad(c):
            if c.shape[0] == n:
                return c
            if c.shape[0] == 0:
                return np.zeros((n,) + c.shape[1:], c.dtype)
            return np.concatenate(
                [c, np.repeat(c[-1:], n - c.shape[0], axis=0)])

        xyz = jnp.asarray(np.stack([pad(c) for c in clouds]))
        f = None if feats is None else jnp.asarray(
            np.stack([pad(np.asarray(x)) for x in feats]))
        return Batch.make(xyz, f, key, n_valid)


def as_batch(batch) -> Batch:
    """Coerce engine.apply input: a Batch passes through; a raw (B, N, 3)
    array becomes a geometry-only batch with default keys."""
    if isinstance(batch, Batch):
        return batch
    arr = jnp.asarray(batch) if not hasattr(batch, "ndim") else batch
    if arr.ndim != 3:
        raise TypeError(
            f"engine.apply expects a Batch or a (B, N, 3) array; got "
            f"shape {getattr(arr, 'shape', None)}")
    return Batch.make(arr)
