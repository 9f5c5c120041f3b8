"""DGCNN classification through the engine against a plain jax.numpy
forward written here: EdgeConv on the exact kNN graph of the points,
the concatenation of every layer's output, the per-point embedding, its
max pool and the head, on seeded weights with nonzero biases."""
import re
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core.mlp import MLP, Dense
from repro.data.synthetic import make_cloud
from repro.engine import Batch, BlockSpec, PCNParams
from repro.models import dgcnn

N, K = 64, 8
SPEC = replace(dgcnn.with_points(dgcnn.DGCNN_C, N), blocks=(
    BlockSpec(N, K, (16,), kind="edge", sampler="all"),
    BlockSpec(N, K, (16,), kind="edge", sampler="all"),
    BlockSpec(N, K, (24,), kind="edge", sampler="all"),
    BlockSpec(N, K, (32,), kind="edge", sampler="all")),
    global_mlp=(48,), head_dims=(32, 16), n_classes=10)
ISL = {"island_size": 8, "island_capacity": 16}      # 8 islands of 64


def _seeded(spec, seed=0):
    """Engine-initialised params with every bias drawn too."""
    p = engine.init(jax.random.PRNGKey(seed), spec)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))

    def biased(mlp):
        return MLP([Dense(l.w, 0.05 * jax.random.normal(next(keys),
                                                        l.b.shape))
                    for l in mlp.layers], mlp.activation)
    return PCNParams(blocks=tuple(map(biased, p.blocks)),
                     head=biased(p.head), global_mlp=biased(p.global_mlp))


@partial(jax.jit, static_argnums=2)
def plain_dgcnn(params, xyz, k):
    """One cloud (n, 3) -> logits: every point a center of its k nearest
    points; per layer relu(max_j([f_j - f_i, f_i] @ W + b)); then the
    embedding of the concatenated outputs, relu of its max over the
    points, and the head (ReLU between its layers)."""
    d = jnp.sum((xyz[:, None, :] - xyz[None, :, :]) ** 2, -1)
    nbr = jnp.argsort(d, axis=1)[:, :k]
    f, outs = xyz, []
    for mlp in params.blocks:
        (layer,) = mlp.layers
        fj = f[nbr]
        fi = jnp.broadcast_to(f[:, None, :], fj.shape)
        x = jnp.concatenate([fj - fi, fi], -1)
        f = jax.nn.relu((x @ layer.w + layer.b).max(1))
        outs.append(f)
    (emb,) = params.global_mlp.layers
    h = jax.nn.relu((jnp.concatenate(outs, -1) @ emb.w + emb.b).max(0))
    for i, layer in enumerate(params.head.layers):
        h = h @ layer.w + layer.b
        if i < len(params.head.layers) - 1:
            h = jax.nn.relu(h)
    return h


def test_dgcnn_c_has_the_published_embedding_and_head():
    p = jax.eval_shape(lambda: engine.init(jax.random.PRNGKey(0),
                                           dgcnn.DGCNN_C))
    assert [m.layers[0].w.shape for m in p.blocks] == [
        (6, 64), (128, 64), (128, 128), (256, 256)]
    assert [l.w.shape for l in p.global_mlp.layers] == [(512, 1024)]
    assert p.global_mlp.activation == "block_end"
    assert [l.w.shape for l in p.head.layers] == [
        (1024, 512), (512, 256), (256, 40)]


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("mode", ["traditional", "lpcn"])
def test_engine_matches_plain_dgcnn_on_a_ragged_batch(mode, backend):
    """Each cloud of a padded batch (64, 50 and 37 valid points) against
    the plain forward of its unpadded points; lpcn's edge compensation is
    exact, so both modes agree with it up to float32 rounding."""
    params = _seeded(SPEC)
    rng = np.random.default_rng(3)
    clouds = [np.asarray(make_cloud(rng, n), np.float32)
              for n in (N, 50, 37)]
    batch = Batch.from_clouds(clouds, key=jax.random.PRNGKey(4), n_pad=N)
    got = jax.jit(partial(engine.apply, spec=SPEC, mode=mode,
                          fc_backend=backend, isl_kw=ISL))(params, batch)
    want = np.stack([plain_dgcnn(params, jnp.asarray(c), K)
                     for c in clouds])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_lpcn_reuses_edges_and_embeds_under_its_own_scope():
    """The lpcn forward reads edges from the hub cache, and the
    embedding's ops carry ``pcn.embed`` beside (not inside) ``pcn.head``."""
    params = _seeded(SPEC)
    xyz = jnp.asarray(np.stack([make_cloud(np.random.default_rng(s), N)
                                for s in range(2)]), jnp.float32)
    batch = Batch.make(xyz, key=jax.random.PRNGKey(1))
    _, reports = jax.jit(partial(engine.apply_with_reports, spec=SPEC,
                                 isl_kw=ISL))(params, batch)
    assert float(np.min(reports.concrete().fetch_saving)) > 0
    text = jax.jit(partial(engine.apply, spec=SPEC, mode="lpcn",
                           isl_kw=ISL)).lower(params, batch).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("pcn.embed" in n for n in names)
    for n in names:
        for part in n.split(";"):
            assert not ("pcn.embed" in part and "pcn.head" in part), part


def test_dgcnn_classification_without_its_embedding_is_refused():
    """A classification spec with no ``global_mlp`` has no path: init
    refuses it rather than building a head over the concatenation."""
    with pytest.raises(ValueError, match="global_mlp"):
        engine.init(jax.random.PRNGKey(0), replace(SPEC, global_mlp=()))


def test_dgcnn_init_draws_every_mlp_from_its_own_key():
    """No two of the blocks, the embedding and the head share a PRNG key:
    their first weights, flattened to a common length, all differ."""
    p = engine.init(jax.random.PRNGKey(0), SPEC)
    firsts = [m.layers[0].w.ravel()[:16]
              for m in (*p.blocks, p.global_mlp, p.head)]
    normed = [np.asarray(w / jnp.linalg.norm(w)) for w in firsts]
    for i in range(len(normed)):
        for j in range(i + 1, len(normed)):
            assert not np.allclose(normed[i], normed[j], atol=1e-3), (i, j)
