"""Ahead-of-time compiles for a described TPU v5e: the FC kernels at the
shapes of full-width ``POINTNET2_C`` and ``DGCNN_C`` forwards (B=8,
N=1024), and the hub schedule at theirs, compiled by the chip's own
compiler with no chip attached.

Interpret mode cannot see Mosaic's layout or VMEM refusals; these
compiles can.  Nothing runs, so they say nothing about results or
speed.  The topology is described inside a module fixture (only the
worker that runs this file loads the TPU compiler) and the tests skip
where it cannot be described.
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import engine
from repro.core.hub_schedule import build_schedule
from repro.core.islandize import Islands
from repro.engine import Batch
from repro.kernels import plans
from repro.kernels.gather_mlp.gather_mlp import (gather_mlp_batched_pallas,
                                                 gather_mlp_pallas)
from repro.kernels.hub_reuse.hub_reuse import (hub_reuse_batched_pallas,
                                               hub_reuse_pallas)
from repro.models.dgcnn import DGCNN_C
from repro.models.pointnet2 import POINTNET2_C

B, N = 8, 1024
SPECS = {"pointnet2_c": POINTNET2_C, "dgcnn_c": DGCNN_C}
#: (model, block) of every FC call site; PointNet++'s keep their ids
BLOCKS = [("pointnet2_c", 0), ("pointnet2_c", 1)] + [
    ("dgcnn_c", i) for i in range(len(DGCNN_C.blocks))]
BLOCK_IDS = ["0", "1"] + [f"dgcnn_c-{i}" for i in range(len(DGCNN_C.blocks))]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler / libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described chip, with the persistent compilation cache
    off: a described-chip compile is written to it but can never be
    read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def kernel_dims():
    """{(model, kernel, block): dims} as the tile planners see them in
    an lpcn forward (traditional mode runs the same gather_mlp shapes).
    Traced with the store bypassed: heuristic plans, fresh kernel
    traces."""
    batch = Batch(jax.ShapeDtypeStruct((B, N, 3), jnp.float32),
                  jax.ShapeDtypeStruct((B, N, 3), jnp.float32),
                  jax.ShapeDtypeStruct((B, 2), jnp.uint32),
                  jax.ShapeDtypeStruct((B,), jnp.int32))
    dims = {}
    for model, spec in SPECS.items():
        params = jax.eval_shape(
            lambda: engine.init(jax.random.PRNGKey(0), spec))
        with plans.bypass(), plans.capture() as log:
            jax.eval_shape(partial(engine.apply, spec=spec, mode="lpcn",
                                   fc_backend="pallas"), params, batch)
        for e in log:
            block = sum(m == model and k == e["kernel"]
                        for m, k, _ in dims)
            dims[model, e["kernel"], block] = e["dims"]
    assert sorted(k for m, k, _ in dims) == sorted(
        ["gather_mlp", "hub_reuse"] * len(BLOCKS)), dims
    return dims


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _weights(sd, d):
    h, f = d["h"], d["f"]
    return (sd((d["d"], h)), sd((h,)), sd((h, f)), sd((f,)))


@pytest.mark.parametrize("batched", [True, False],
                         ids=["batched", "per_cloud"])
@pytest.mark.parametrize("masked", [True, False],
                         ids=["masked", "unmasked"])
@pytest.mark.parametrize("block", BLOCKS, ids=BLOCK_IDS)
def test_gather_mlp_compiles(one_chip, kernel_dims, block, masked, batched):
    """DGCNN's edge rows fill every lane from layer 2 on (D = 2F = 128,
    256): the kernel's center subtract then spans the whole row."""
    d = kernel_dims[block[0], "gather_mlp", block[1]]

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lead = (d["b"],) if batched else ()
    kern = gather_mlp_batched_pallas if batched else gather_mlp_pallas
    data = [sd(lead + (d["s"], d["k"], d["d"])),
            sd(lead + (d["s"], d["dc"]))]
    if masked:
        data.append(sd(lead + (d["s"], d["k"]), jnp.int32))

    def fn(raw, ctr, *rest):
        mask = rest[0] if masked else None
        w = rest[1:] if masked else rest
        return kern(raw, ctr, *w, interpret=False, mask=mask)

    _compile(fn, *data, *_weights(sd, d))


@pytest.mark.parametrize("batched", [True, False],
                         ids=["batched", "per_cloud"])
@pytest.mark.parametrize("masked", [True, False],
                         ids=["masked", "unmasked"])
@pytest.mark.parametrize("block", BLOCKS, ids=BLOCK_IDS)
def test_hub_reuse_compiles(one_chip, kernel_dims, block, masked, batched):
    d = kernel_dims[block[0], "hub_reuse", block[1]]

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lead = (d["b"],) if batched else ()
    kern = hub_reuse_batched_pallas if batched else hub_reuse_pallas
    mk = lead + (d["hn"], d["m"], d["k"])
    data = [sd(lead + (d["hn"], d["c"], d["d"])), sd(mk, jnp.int32),
            sd(lead + (d["hn"], d["m"], d["f"]))]
    if masked:
        data.append(sd(mk, jnp.int32))

    def fn(pool, slot, comp, *rest):
        live = rest[0] if masked else None
        w = rest[1:] if masked else rest
        return kern(pool, slot, comp, *w, interpret=False, live=live)

    _compile(fn, *data, *_weights(sd, d))


def _traditional_forward(one_chip, monkeypatch, spec):
    def sd(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(sd, jax.eval_shape(
        lambda: engine.init(jax.random.PRNGKey(0), spec)))
    batch = Batch(sd(jnp.zeros((B, N, 3))), sd(jnp.zeros((B, N, 3))),
                  sd(jnp.zeros((B, 2), jnp.uint32)),
                  sd(jnp.zeros((B,), jnp.int32)))
    # the kernel wrappers pick Mosaic when the backend is a TPU; the
    # bypass drops (on entry and exit) any interpret-mode kernel trace
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with plans.bypass():
        compiled = _compile(partial(engine.apply, spec=spec,
                                    mode="traditional", fc_backend="pallas"),
                            params, batch)
    return compiled.as_text().count("tpu_custom_call")


def test_traditional_forward_compiles(one_chip, monkeypatch):
    """The whole traditional ``POINTNET2_C`` forward at B=8, N=1024
    compiles for the chip with both blocks' gather_mlp kernels in it.
    (The lpcn forward takes about a minute to compile on a CPU host and
    is left to ``chip_smoke.py``.)"""
    assert _traditional_forward(one_chip, monkeypatch, POINTNET2_C) >= 2


def test_dgcnn_traditional_forward_compiles(one_chip, monkeypatch):
    """The traditional ``DGCNN_C`` forward (four EdgeConv layers, the
    embedding and the head) compiles with its four gather_mlp kernels."""
    assert _traditional_forward(one_chip, monkeypatch, DGCNN_C) >= 4


def test_traditional_step_names_its_kernels_and_stages(one_chip,
                                                       monkeypatch):
    """The engine's step compiled for the chip: the program is
    ``jit_pcn_step``, the kernels' custom calls keep their names (a trace
    names their events after them), and the stage scopes survive in the
    op metadata the trace carries as ``tf_op``."""
    import re

    def sd(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    eng = engine.PCNEngine(POINTNET2_C, mode="traditional",
                           fc_backend="pallas")
    params = jax.tree.map(sd, jax.eval_shape(
        lambda: engine.init(jax.random.PRNGKey(0), POINTNET2_C)))
    batch = Batch(sd(jnp.zeros((B, N, 3))), sd(jnp.zeros((B, N, 3))),
                  sd(jnp.zeros((B, 2), jnp.uint32)),
                  sd(jnp.zeros((B,), jnp.int32)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with plans.bypass():
        text = eng._japply.lower(params, batch).compile().as_text()
    assert re.search(r"^HloModule jit_pcn_step\b", text, re.M)
    calls = re.findall(r"^\s*%(\w+)\.\d+ = \S+ custom-call\(", text, re.M)
    assert sorted(calls) == ["gather_mlp", "gather_mlp"]
    scopes = set(re.findall(r'op_name="[^"]*pcn\.([a-z_]+)', text))
    assert {"sample", "neighbors", "dense_inputs", "head"} <= scopes


#: (S, K, H, M, C) of every production hub schedule: pointnet2_c's two
#: blocks and dgcnn_c's EdgeConv layers
SCHEDULES = {"pn2c-1": (512, 32, 16, 64, 64), "pn2c-2": (128, 64, 4, 64, 128),
             "dgcnn": (1024, 20, 32, 64, 40)}


@pytest.mark.parametrize("shape", list(SCHEDULES), ids=list(SCHEDULES))
def test_hub_schedule_compiles_without_pairwise_temporaries(one_chip, shape):
    """The schedule's id compares fuse into their reductions: a batch of
    16 clouds needs under 8 MiB of temporaries, where one materialised
    (M·K)² compare per island would take 0.8 to 1 GiB."""
    S, K, H, M, C = SCHEDULES[shape]
    b = 16

    def sd(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    islands = Islands(sd((b, H, M)), sd((b, H)), sd((b, S), jnp.bool_),
                      sd((b, S)))
    compiled = jax.jit(jax.vmap(partial(build_schedule, cache_capacity=C))
                       ).lower(islands, sd((b, S, K))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2**20
