"""Per-kernel allclose vs. pure-jnp oracles, swept over shapes/dtypes
(interpret mode on CPU; the same kernels compile via Mosaic on TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("s,n,k,tc,tp", [
    (64, 256, 8, 64, 128),
    (130, 1000, 32, 128, 256),   # ragged tiles both axes
    (32, 512, 16, 32, 512),
    (16, 100, 4, 16, 64),
])
def test_knn_kernel(s, n, k, tc, tp):
    from repro.kernels.knn.ops import knn, knn_ref
    c = jnp.asarray(RNG.normal(size=(s, 3)), jnp.float32)
    p = jnp.asarray(RNG.normal(size=(n, 3)), jnp.float32)
    d1, i1 = knn(c, p, k, tc=tc, tp=tp, interpret=True)
    d0, i0 = knn_ref(c, p, k)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d0),
                               rtol=1e-5, atol=1e-5)
    unique_d = np.asarray(jnp.abs(d0[:, 1:] - d0[:, :-1]) > 1e-9)
    agree = (np.asarray(i1) == np.asarray(i0))[:, 1:][unique_d]
    assert agree.mean() > 0.99  # ties may reorder


@pytest.mark.parametrize("s,k,d,dc,h,f,dtype", [
    (37, 32, 6, 3, 64, 128, jnp.float32),
    (8, 16, 10, 3, 32, 64, jnp.float32),
    (64, 20, 12, 6, 48, 96, jnp.float32),
])
def test_gather_mlp_kernel(s, k, d, dc, h, f, dtype):
    from repro.kernels.gather_mlp.ops import gather_mlp, gather_mlp_ref
    raw = jnp.asarray(RNG.normal(size=(s, k, d)), dtype)
    ctr = jnp.asarray(RNG.normal(size=(s, dc)), dtype)
    w1 = jnp.asarray(RNG.normal(size=(d, h)) * 0.1, dtype)
    w2 = jnp.asarray(RNG.normal(size=(h, f)) * 0.1, dtype)
    b1 = jnp.asarray(RNG.normal(size=(h,)) * 0.01, dtype)
    b2 = jnp.asarray(RNG.normal(size=(f,)) * 0.01, dtype)
    y1 = gather_mlp(raw, ctr, w1, b1, w2, b2, ts=8, interpret=True)
    y0 = gather_mlp_ref(raw, ctr, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("batched", [False, True],
                         ids=["per_cloud", "batched"])
def test_gather_mlp_kernel_center_spans_the_row(batched):
    """An EdgeConv row at a lane-aligned width: the center covers all
    D = 128 lanes (dc == D), and the kernel gives what the subtract
    followed by a concatenation of the (empty) rest of the row gives."""
    from repro.kernels.gather_mlp.gather_mlp import (
        gather_mlp_batched_pallas, gather_mlp_pallas)
    s, k, d, h, f = 16, 8, 128, 32, 64
    raw = jnp.asarray(RNG.normal(size=(s, k, d)), jnp.float32)
    ctr = jnp.asarray(RNG.normal(size=(s, d)), jnp.float32)
    w1 = jnp.asarray(RNG.normal(size=(d, h)) * 0.1, jnp.float32)
    w2 = jnp.asarray(RNG.normal(size=(h, f)) * 0.1, jnp.float32)
    b1 = jnp.asarray(RNG.normal(size=(h,)) * 0.01, jnp.float32)
    b2 = jnp.asarray(RNG.normal(size=(f,)) * 0.01, jnp.float32)
    x = jnp.concatenate([raw[..., :d] - ctr[:, None, :], raw[..., d:]], -1)
    want = (jax.nn.relu(x @ w1 + b1) @ w2 + b2).max(1)
    if batched:
        got = gather_mlp_batched_pallas(raw[None], ctr[None], w1, b1, w2,
                                        b2, ts=8, interpret=True)[0]
    else:
        got = gather_mlp_pallas(raw, ctr, w1, b1, w2, b2, ts=8,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("hn,c,m,k,d,hd,f", [
    (4, 64, 16, 32, 6, 64, 128),
    (2, 32, 8, 16, 9, 32, 64),
    (1, 16, 4, 8, 6, 16, 32),
])
def test_hub_reuse_kernel(hn, c, m, k, d, hd, f):
    from repro.kernels.hub_reuse.ops import hub_reuse, hub_reuse_ref
    pool = jnp.asarray(RNG.normal(size=(hn, c, d)), jnp.float32)
    slot = jnp.asarray(RNG.integers(-1, c, (hn, m, k)), jnp.int32)
    comp = jnp.asarray(RNG.normal(size=(hn, m, f)) * 0.01, jnp.float32)
    w1 = jnp.asarray(RNG.normal(size=(d, hd)) * 0.1, jnp.float32)
    w2 = jnp.asarray(RNG.normal(size=(hd, f)) * 0.1, jnp.float32)
    b1, b2 = jnp.zeros(hd), jnp.zeros(f)
    z1 = hub_reuse(pool, slot, comp, w1, b1, w2, b2, interpret=True)
    z0 = hub_reuse_ref(pool, slot, comp, w1, b1, w2, b2)
    np.testing.assert_allclose(np.asarray(z1), np.asarray(z0),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("backend,interpret,expected", [
    ("cpu", None, True),
    ("tpu", None, False),
    ("gpu", True, True),        # an explicit flag is honoured anywhere
    ("tpu", True, True),
    ("cpu", False, False),
])
def test_interpret_resolution(monkeypatch, backend, interpret, expected):
    from repro.kernels import resolve_interpret
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert resolve_interpret(interpret) is expected


@pytest.mark.parametrize("backend", ["gpu", "METAL"])
def test_interpret_resolution_refuses_other_platforms(monkeypatch, backend):
    """A kernel wrapper left at interpret=None never falls back to the
    interpreter on a platform that is neither cpu nor tpu."""
    from repro.kernels import resolve_interpret
    from repro.kernels.gather_mlp.ops import gather_mlp_batched
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match="no Pallas lowering"):
        resolve_interpret(None)
    # shapes no other test traces, so the jit cache cannot hide the check
    raw = jnp.zeros((1, 3, 5, 7), jnp.float32)
    ctr = jnp.zeros((1, 3, 3), jnp.float32)
    w = (jnp.zeros((7, 11)), jnp.zeros(11), jnp.zeros((11, 13)),
         jnp.zeros(13))
    with pytest.raises(RuntimeError, match="no Pallas lowering"):
        gather_mlp_batched(raw, ctr, *w)


@pytest.mark.parametrize("b,hq,hkv,s,d,causal", [
    (1, 2, 1, 128, 32, True),
    (2, 4, 2, 256, 64, True),
    (1, 4, 4, 64, 32, False),
    (1, 8, 2, 192, 16, True),     # ragged q tiles
])
def test_flash_attention_kernel(b, hq, hkv, s, d, causal):
    from repro.kernels.flash_attention.ops import (attention_ref,
                                                   flash_attention)
    q = jnp.asarray(RNG.normal(size=(b, hq, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)), jnp.float32)
    a1 = flash_attention(q, k, v, causal=causal, tq=64, tk=64,
                         interpret=True)
    a0 = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention.ops import (attention_ref,
                                                   flash_attention)
    q = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(1, 1, 128, 32)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(1, 1, 128, 32)), jnp.bfloat16)
    a1 = flash_attention(q, k, v, tq=64, tk=64, interpret=True)
    a0 = attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(a1, np.float32), np.asarray(a0, np.float32),
        rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("bs,nc,q,h,p,s", [
    (1, 2, 16, 2, 8, 16),
    (2, 1, 32, 4, 16, 32),
])
def test_ssd_chunk_kernel(bs, nc, q, h, p, s):
    from repro.kernels.ssd_chunk.ops import ssd_chunk, ssd_chunk_ref
    x = jnp.asarray(RNG.normal(size=(bs, nc, q, h, p)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(bs, nc, q, s)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(bs, nc, q, s)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 1.0, (bs, nc, q, h)), jnp.float32)
    # cum must be non-increasing within a chunk (dA < 0)
    cum = -jnp.cumsum(jnp.asarray(
        RNG.uniform(0.01, 0.2, (bs, nc, q, h)), jnp.float32), axis=2)
    y1, st1 = ssd_chunk(x, B, C, dt, cum, interpret=True)
    y0, st0 = ssd_chunk_ref(x, B, C, dt, cum)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st0),
                               rtol=2e-4, atol=2e-4)
