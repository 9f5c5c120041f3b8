"""The program names its own work for a profiler: each stage of a step
runs under a flat ``pcn.<stage>`` scope that the compiled program keeps
in its op metadata, the engine's step is the named ``jit(pcn_step)``,
and the server writes ``serve.*`` host spans, the spans of one batch
sharing its ``seq``."""
import glob
import re
from collections import defaultdict
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro import engine
from repro.data.synthetic import make_cloud
from repro.engine import BlockSpec
from repro.models import pointnet2
from repro.serve import BucketSet, PCNServer

SPEC = replace(pointnet2.POINTNET2_C, blocks=(
    BlockSpec(32, 8, (16, 32)), BlockSpec(8, 8, (32, 48))))
LPCN_ONLY = {"islandize", "schedule", "reuse_inputs", "overflow"}
BOTH = {"sample", "neighbors", "dense_inputs", "head"}
SCOPE = re.compile(r"pcn\.([a-z_]+)")


def _compiled(spec, mode, backend="pallas"):
    eng = engine.PCNEngine(spec, mode=mode, fc_backend=backend)
    params = eng.init(jax.random.PRNGKey(0))
    xyz = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 3))
    batch = engine.Batch.make(xyz, key=jax.random.PRNGKey(2))
    return eng, params, batch, eng._japply.lower(params, batch)


def _scopes(hlo_text: str) -> set[str]:
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {s for n in names for s in SCOPE.findall(n)}


@pytest.mark.parametrize("mode,expected,absent", [
    ("lpcn", BOTH | LPCN_ONLY, set()),
    ("traditional", BOTH, LPCN_ONLY),
])
def test_compiled_step_carries_its_stage_scopes(mode, expected, absent):
    *_, lowered = _compiled(SPEC, mode)
    text = lowered.compile().as_text()
    found = _scopes(text)
    assert expected <= found, expected - found
    assert not found & absent
    assert re.search(r"^HloModule jit_pcn_step\b", text, re.M)


def test_no_stage_scope_encloses_another():
    *_, lowered = _compiled(SPEC, "lpcn")
    names = re.findall(r'op_name="([^"]*)"',
                       lowered.compile().as_text())
    assert names
    for n in names:                 # a fused op joins its ops' names
        for part in n.split(";"):
            assert len(SCOPE.findall(part)) <= 1, part


def test_octree_scope_where_a_component_reads_the_tree():
    """FPS and the brute-force kNN never read the input octree, so XLA
    drops its build; a tree-narrowed kNN keeps it under ``pcn.octree``."""
    spec = replace(SPEC, blocks=tuple(replace(b, neighbor="hgpcn")
                                      for b in SPEC.blocks))
    *_, lowered = _compiled(spec, "traditional", backend="reference")
    assert "octree" in _scopes(lowered.compile().as_text())
    *_, lowered = _compiled(SPEC, "traditional", backend="reference")
    assert "octree" not in _scopes(lowered.compile().as_text())


def test_kernels_keep_their_names_in_the_step():
    """The FC kernels' pallas_calls are named after their kernels (the
    names a TPU trace gives their custom calls), whatever scope the call
    sits in."""
    eng, params, batch, _ = _compiled(SPEC, "lpcn")
    jaxpr = jax.make_jaxpr(eng._japply)(params, batch)
    names = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(set(names)) == ["gather_mlp", "hub_reuse"], names


def test_compile_once_with_the_named_step():
    eng, params, batch, _ = _compiled(SPEC, "traditional", "reference")
    eng.apply(params, batch)
    eng.apply(params, batch)
    assert eng.compile_count == 1


def test_server_spans_in_the_host_plane(tmp_path):
    from jax.profiler import ProfileData
    eng = engine.PCNEngine(SPEC, mode="lpcn", fc_backend="reference")
    params = eng.init(jax.random.PRNGKey(0))
    srv = PCNServer(eng, params, BucketSet.make([64], batch=2),
                    timeout_s=0.0, fallback=None)
    rng = np.random.default_rng(0)
    clouds = [np.asarray(make_cloud(rng, n), np.float32)
              for n in (40, 50, 60)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        rids = [srv.submit(c) for c in clouds]
        srv.poll()
        srv.drain()
    finally:
        jax.profiler.stop_trace()
    for r in rids:
        srv.take(r)
    srv.close()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    by_seq, admits = defaultdict(set), []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("serve."):
                    continue
                stats = dict(ev.stats)
                if ev.name == "serve.admit":
                    admits.append(stats["rid"])
                else:
                    by_seq[stats["seq"]].add(ev.name)
    assert sorted(admits) == sorted(rids)
    # one full batch of two, one partial batch of one
    assert sorted(by_seq) == [0, 1]
    for names in by_seq.values():
        assert names == {"serve.fire", "serve.pad", "serve.device",
                         "serve.readback", "serve.complete"}
