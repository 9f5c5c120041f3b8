"""DGCNN [48] — EdgeConv benchmark, (c) classification / (s) segmentation.

EdgeConv: every point is a center (sampler="all"), k=20, MLP input
[f_j − f_i, f_i] (the same map as the published h(x_i, x_j − x_i)).
DGCNN(c) is the classification network of the paper's Figure 3
(arXiv 1801.07829): four EdgeConv layers 64/64/128/256 on 1,024 points,
their outputs concatenated (512 per point), a shared per-point layer
512 → 1,024 (``global_mlp``), a global max pool, and the head
512 → 256 → 40.

Departures from the published network:

* static graph: the neighbor graph is built once in coordinate space
  and used by every layer (the paper rebuilds it in feature space at
  layers 2-4).  This reproduction's own choice, not a published variant:
  octree islands are spatial, so a feature-space graph would bypass
  them;
* ReLU in place of LeakyReLU(0.2); both are monotone, so pooling before
  the activation stays exact;
* batch norm folded into the linear layers, dropout left out (inference);
* max pool only (the paper's Figure 3; the PyTorch release also
  concatenates an average pool);
* exact kNN ("pointacc") for the neighbor search.

DGCNN(c) applies its activation at block end, which makes L-PCN's delta
compensation exact (paper §VI-E).
"""
from __future__ import annotations

from .common import BlockSpec, PCNSpec

DGCNN_C = PCNSpec(
    name="dgcnn_c",
    blocks=(
        BlockSpec(1024, 20, (64,), kind="edge", sampler="all"),
        BlockSpec(1024, 20, (64,), kind="edge", sampler="all"),
        BlockSpec(1024, 20, (128,), kind="edge", sampler="all"),
        BlockSpec(1024, 20, (256,), kind="edge", sampler="all"),
    ),
    head_dims=(512, 256),
    n_classes=40,
    global_mlp=(1024,),       # per-point embedding before the max pool
    activation="block_end",   # -> exact delta compensation (paper §VI-E)
)

DGCNN_S = PCNSpec(
    name="dgcnn_s",
    blocks=(
        BlockSpec(8192, 20, (64,), kind="edge", sampler="all"),
        BlockSpec(8192, 20, (64,), kind="edge", sampler="all"),
        BlockSpec(8192, 20, (64,), kind="edge", sampler="all"),
    ),
    head_dims=(256, 128),
    n_classes=20,
    in_feats=6,
    task="seg",
    activation="block_end",
)


def with_points(spec: PCNSpec, n: int) -> PCNSpec:
    """Rescale an `all`-sampler spec to an n-point cloud."""
    from dataclasses import replace
    return replace(spec, blocks=tuple(
        BlockSpec(n, b.k, b.mlp_dims, b.radius, b.kind, b.sampler,
                  b.neighbor) for b in spec.blocks))

# The PR-1 ``init``/``apply``/``init_for_task`` dict shims completed
# their one-more-cycle deprecation window and are gone: use
# ``repro.engine.init`` (builds the task-correct concat head) /
# ``engine.apply`` / ``engine.apply_single``.
