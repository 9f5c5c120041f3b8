"""Ahead-of-time compiles for a described TPU v5e: the FC kernels at the
shapes of a full-width ``POINTNET2_C`` forward (B=8, N=1024), compiled
by the chip's own compiler with no chip attached.

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
from repro.engine import Batch
from repro.kernels import plans
from repro.kernels.gather_mlp.gather_mlp import (gather_mlp_batched_pallas,
                                                 gather_mlp_pallas)
from repro.kernels.hub_reuse.hub_reuse import (hub_reuse_batched_pallas,
                                               hub_reuse_pallas)
from repro.models.pointnet2 import POINTNET2_C

B, N = 8, 1024


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
    """{(kernel, block): dims} as the tile planners see them in an lpcn
    forward (traditional mode runs the same gather_mlp shapes).  Traced
    with the store bypassed: heuristic plans, fresh kernel traces."""
    params = jax.eval_shape(
        lambda: engine.init(jax.random.PRNGKey(0), POINTNET2_C))
    batch = Batch(jax.ShapeDtypeStruct((B, N, 3), jnp.float32),
                  jax.ShapeDtypeStruct((B, N, 3), jnp.float32),
                  jax.ShapeDtypeStruct((B, 2), jnp.uint32),
                  jax.ShapeDtypeStruct((B,), jnp.int32))
    with plans.bypass(), plans.capture() as log:
        jax.eval_shape(partial(engine.apply, spec=POINTNET2_C, mode="lpcn",
                               fc_backend="pallas"), params, batch)
    dims = {}
    for e in log:
        block = sum(k == e["kernel"] for k, _ in dims)
        dims[e["kernel"], block] = e["dims"]
    assert sorted(dims) == [("gather_mlp", 0), ("gather_mlp", 1),
                            ("hub_reuse", 0), ("hub_reuse", 1)], dims
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
@pytest.mark.parametrize("block", [0, 1])
def test_gather_mlp_compiles(one_chip, kernel_dims, block, masked, batched):
    d = kernel_dims["gather_mlp", block]

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
@pytest.mark.parametrize("block", [0, 1])
def test_hub_reuse_compiles(one_chip, kernel_dims, block, masked, batched):
    d = kernel_dims["hub_reuse", block]

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


def test_traditional_forward_compiles(one_chip, monkeypatch):
    """The whole traditional ``POINTNET2_C`` forward at B=8, N=1024
    compiles for the chip with both blocks' gather_mlp kernels in it.
    (The lpcn forward takes about a minute to compile on a CPU host and
    is left to ``chip_smoke.py``.)"""
    def sd(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(sd, jax.eval_shape(
        lambda: engine.init(jax.random.PRNGKey(0), POINTNET2_C)))
    batch = Batch(sd(jnp.zeros((B, N, 3))), sd(jnp.zeros((B, N, 3))),
                  sd(jnp.zeros((B, 2), jnp.uint32)),
                  sd(jnp.zeros((B,), jnp.int32)))
    # the kernel wrappers pick Mosaic when the backend is a TPU; the
    # bypass drops (on entry and exit) any interpret-mode kernel trace
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with plans.bypass():
        compiled = _compile(partial(engine.apply, spec=POINTNET2_C,
                                    mode="traditional", fc_backend="pallas"),
                            params, batch)
    assert compiled.as_text().count("tpu_custom_call") >= 2


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
