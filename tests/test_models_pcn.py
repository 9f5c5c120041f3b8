"""PCN model smoke tests (reduced clouds) + workload reports, through
the engine API (the PR-1 ``models.*.init``/``apply`` shims are gone)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.data.synthetic import make_cloud
from repro.models import dgcnn, pointnet2, pointnext, pointvector

KEY = jax.random.PRNGKey(0)


def _cloud(n, f=3, seed=0):
    rng = np.random.default_rng(seed)
    xyz = jnp.asarray(make_cloud(rng, n))
    if f > 3:
        feats = jnp.concatenate(
            [xyz, jnp.asarray(rng.uniform(0, 1, (n, f - 3)),
                              jnp.float32)], -1)
    else:
        feats = xyz
    return xyz, feats


def test_pointnet2_cls():
    xyz, feats = _cloud(512)
    from dataclasses import replace
    from repro.models.common import BlockSpec
    spec = replace(pointnet2.POINTNET2_C, blocks=(
        BlockSpec(128, 16, (32, 64)), BlockSpec(32, 16, (64, 128))))
    p = engine.init(KEY, spec)
    logits, rep = engine.apply_single(p, xyz, feats, KEY, spec=spec,
                                      mode="lpcn", with_report=True)
    assert logits.shape == (40,)
    assert bool(jnp.isfinite(logits).all())
    assert rep.concrete().fetch_saving > 0


def test_pointnet2_seg():
    xyz, feats = _cloud(512, f=6, seed=1)
    from dataclasses import replace
    from repro.models.common import BlockSpec
    spec = replace(pointnet2.POINTNET2_S, blocks=(
        BlockSpec(128, 16, (32, 64)), BlockSpec(32, 16, (64, 128))))
    p = engine.init(KEY, spec)
    logits, _ = engine.apply_single(p, xyz, feats, KEY, spec=spec,
                                    mode="traditional")
    assert logits.shape == (512, 13)
    assert bool(jnp.isfinite(logits).all())


def test_dgcnn_cls_exact_reuse():
    """DGCNN(c) uses block_end activation -> islandized output must match
    traditional (paper §VI-E)."""
    xyz, feats = _cloud(256, seed=2)
    spec = dgcnn.with_points(dgcnn.DGCNN_C, 256)
    p = engine.init(KEY, spec)
    # the head reads the pooled 512 -> 1,024 embedding
    assert p.global_mlp.layers[0].w.shape == (512, 1024)
    assert p.head.layers[0].w.shape == (1024, 512)
    l1, _ = engine.apply_single(p, xyz, feats, KEY, spec=spec, mode="lpcn")
    l0, _ = engine.apply_single(p, xyz, feats, KEY, spec=spec,
                                mode="traditional")
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=2e-3, atol=2e-3)


def test_pointnext():
    xyz, feats = _cloud(512, f=6, seed=3)
    from dataclasses import replace
    from repro.models.common import BlockSpec
    spec = replace(pointnext.POINTNEXT_S, blocks=(
        BlockSpec(128, 16, (32,)), BlockSpec(32, 16, (64,))))
    p = engine.init(KEY, spec)
    logits, rep = engine.apply_single(p, xyz, feats, KEY, spec=spec,
                                      mode="lpcn", with_report=True)
    assert logits.shape == (512, 13)
    assert bool(jnp.isfinite(logits).all())


def test_pointvector():
    xyz, feats = _cloud(512, f=6, seed=4)
    from dataclasses import replace
    from repro.models.common import BlockSpec
    spec = replace(pointvector.POINTVECTOR_L, blocks=(
        BlockSpec(128, 16, (48,)), BlockSpec(32, 16, (96,))))
    p = engine.init(KEY, spec)
    logits, _ = engine.apply_single(p, xyz, feats, KEY, spec=spec,
                                    mode="lpcn")
    assert logits.shape == (512, 13)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("method", ["pointacc", "hgpcn", "edgepc",
                                    "crescent"])
def test_ds_method_recall(method):
    """Approximate DS methods must overlap heavily with exact KNN."""
    from repro.core.pipeline import LPCNConfig, data_structuring
    xyz, _ = _cloud(512, seed=5)
    cfg = LPCNConfig(n_centers=64, k=8, neighbor=method)
    cidx, nbr = data_structuring(cfg, xyz, KEY)
    cfg0 = LPCNConfig(n_centers=64, k=8, neighbor="pointacc")
    _, nbr0 = data_structuring(cfg0, xyz, KEY)
    recall = np.mean([
        len(set(a.tolist()) & set(b.tolist())) / 8
        for a, b in zip(np.asarray(nbr), np.asarray(nbr0))])
    assert recall > (0.99 if method in ("pointacc", "hgpcn") else 0.5)
