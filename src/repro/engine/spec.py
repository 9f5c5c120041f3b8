"""Model specifications — static, hashable descriptions of a PCN.

A :class:`PCNSpec` is pure Python data (ints/strings/tuples), so it can be
closed over by ``jax.jit`` (one compiled executable per spec) and drives
all shape decisions statically.  Moved here from ``repro.models.common``
so the engine owns the public API surface; ``models.common`` re-exports
for backward compatibility.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class BlockSpec:
    """One building block (SA or EdgeConv) of a PCN."""
    n_centers: int
    k: int
    mlp_dims: tuple            # hidden+out dims, input inferred
    radius: float = 0.2
    kind: str = "sa"           # sa | edge
    sampler: str = "fps"
    neighbor: str = "pointacc"


@dataclass(frozen=True)
class PCNSpec:
    """A whole point-cloud network."""
    name: str
    blocks: tuple              # tuple[BlockSpec]
    head_dims: tuple           # classifier / per-point head
    n_classes: int
    in_feats: int = 3          # input feature dim (xyz counts as features)
    task: str = "cls"          # cls | seg
    global_mlp: tuple = ()     # final global SA mlp / DGCNN embedding (cls)
    activation: str = "per_layer"   # per_layer | block_end (paper §VI-E)


def block_in_dim(kind: str, f_prev: int) -> int:
    return (3 + f_prev) if kind == "sa" else (2 * f_prev)


def arch_of(spec: PCNSpec) -> str:
    """Architecture family a spec belongs to (drives init/forward
    dispatch).  Unknown names fall back to the generic SA-stack family
    ("pointnet2"), which covers ad-hoc specs built in tests/examples."""
    return spec.name.split("_")[0]
