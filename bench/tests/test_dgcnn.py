"""The dgcnn family: its counts by hand, its files found by name, and its
plain reference against the program and the control at the rehearsal's
size (``run --rehearse``: 256 points, every one a center)."""
import contextlib
import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families, gen, program, run, weights
from bench import refcore as rc
from bench.families import dgcnn

CELL = "dgcnn-lpcn-b16"


def cfg():
    with open(os.path.join(run.BENCH, "configs", "dgcnn_c.json")) as f:
        return json.load(f)


def test_dgcnn_c_model_flops_by_hand():
    # edge maps 1024*20 * 2*(6*64 + 128*64 + 128*128 + 256*256), the
    # embedding 1024 * 2*512*1024, the head 2*(1024*512 + 512*256 + 256*40)
    edge = 1024 * 20 * 2 * (6 * 64 + 128 * 64 + 128 * 128 + 256 * 256)
    embed = 1024 * 2 * 512 * 1024
    head = 2 * (1024 * 512 + 512 * 256 + 256 * 40)
    assert edge == pytest.approx(3.71e9, rel=1e-3)
    assert embed == pytest.approx(1.07e9, rel=1e-2)
    assert head == pytest.approx(1.33e6, rel=1e-2)
    assert dgcnn.model_flops_per_cloud(cfg()) == edge + embed + head
    assert dgcnn.model_flops_per_cloud(cfg()) == pytest.approx(4.78e9,
                                                               rel=1e-2)


def test_dgcnn_c_kernel_counts():
    c = cfg()
    g = dgcnn.gather_mlp_calls(c)
    h = dgcnn.hub_reuse_calls(c)
    assert len(g) == len(h) == 4
    # every point's 20 edges through each layer's edge map
    assert [x["flops"] for x in g] == [1024 * 20 * 2 * a * b for a, b in
                                       ((6, 64), (128, 64), (128, 128),
                                        (256, 256))]
    # 32 islands x 40 cached points through the same maps
    assert h[3]["flops"] == 32 * 40 * 2 * 256 * 256
    # layer 4: neighbours' and centers' features, slot mask, result and
    # weights
    assert g[3]["bytes"] == (1024 * 20 * 128 * 4 + 1024 * 128 * 4
                             + 1024 * 20 * 4 + 1024 * 256 * 4
                             + (256 * 256 + 256) * 4)
    # cached points' and hubs' features, slots, compensation in, result
    assert h[3]["bytes"] == (32 * 40 * 128 * 4 + 32 * 128 * 4
                             + 32 * 64 * 20 * 4 + 2 * 32 * 64 * 256 * 4
                             + (256 * 256 + 256) * 4)


def test_discovery_finds_the_cell_and_its_files():
    cell = run.load_cell(CELL)
    assert cell["config"]["name"] == "dgcnn_c"
    assert families.of(cell["config"]) is dgcnn
    assert cell["traffic"]["engine"]["mode"] == "lpcn"
    assert "logit_gap" in cell["limits"]
    names = {m["name"] for m in cell["per_layer"]}
    assert "stage_ms.embed" in names and "stage_ms.sample" not in names
    assert {"hub_reuse_roofline", "gather_mlp_roofline", "mfu_pct",
            "stage_ms.islandize", "stage_ms.schedule"} <= names
    assert [m["name"] for m in cell["end_to_end"]] == ["setup_s",
                                                      "clouds_per_s"]
    assert run.metric_reader("stage_ms.embed")({}) is None


def test_program_takes_the_benchmark_weights():
    """The program's params built from the benchmark's weights have the
    published shapes: four edge maps, the 512 -> 1,024 embedding and the
    head."""
    program.import_program()
    c = cfg()
    w = weights.make(c, gen.jax_key_words(5, 1)[0])
    p = program.params(c, w)
    assert [m.layers[0].w.shape for m in p.blocks] == [
        (6, 64), (128, 64), (128, 128), (256, 256)]
    assert p.global_mlp.layers[0].w.shape == (512, 1024)
    assert [l.w.shape for l in p.head.layers] == [(1024, 512), (512, 256),
                                                  (256, 40)]


def test_knn_ways_reads_a_tie_at_the_kth_neighbour_each_way():
    # from point 0, points 2 and 3 tie for its third-nearest slot; from
    # point 1 they tie too; nowhere else does the third slot tie
    pts = np.array([[0, 0, 0], [1, 0, 0], [0, 2, 0], [0, 0, 2],
                    [9, 9, 9]], np.float32)
    ways = dgcnn.knn_ways(pts, 3)
    assert len(ways) == 3
    assert ways[0][:2].tolist() == [[0, 1, 2], [1, 0, 2]]
    assert ways[1][0].tolist() == [0, 1, 3]
    assert ways[2][1].tolist() == [1, 0, 3]
    assert (ways[1][1:] == ways[0][1:]).all()


def tiny():
    cell = run.load_cell(CELL)
    run.shrink(cell)
    return cell


@pytest.mark.parametrize("mode", ["lpcn", "traditional"])
def test_reference_matches_the_program(mode):
    """The plain reference against ``engine.apply`` on the rehearsal's
    clouds, in both modes (on the CPU they agree to rounding)."""
    program.import_program()
    from repro import engine
    cell = tiny()
    c = cell["config"]
    assert c["points"] == 256 and all(b["n_centers"] == 256
                                      for b in c["blocks"])
    w = weights.make(c, gen.jax_key_words(9, 1)[0])
    clouds = np.stack(gen.make_clouds(gen.rng_for(9, 2), [256] * 2))
    keys = gen.jax_key_words(9, 3, n=2)
    eng = engine.PCNEngine(program.spec(c), mode=mode, fc_backend="pallas",
                           isl_kw=program.isl_kw(c))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(eng.apply(program.params(c, w), engine.Batch.make(
            jnp.asarray(clouds), key=jnp.asarray(keys))))
    ref = dgcnn.reference(c, w, list(clouds), keys, mode)
    assert rc.rel_gap(got, ref).max() < cell["limits"]["logit_gap"]["limit"]


def test_the_control_fails_the_limit():
    cell = tiny()
    c = cell["config"]
    w = weights.make(c, gen.jax_key_words(7, 1)[0])
    clouds = gen.make_clouds(gen.rng_for(7, 2), [c["points"]] * 4)
    keys = gen.jax_key_words(7, 3, n=4)
    ref = dgcnn.reference(c, w, clouds, keys, "lpcn")
    ctl = dgcnn.reference(c, w, clouds, keys, "lpcn", precision="high")
    assert rc.rel_gap(ctl[:, 0], ref).max() > \
        cell["limits"]["logit_gap"]["limit"]


def test_rehearsal_is_correct():
    if jax.devices()[0].platform != "cpu":
        pytest.skip("the rehearsal runs on the CPU")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "2",
                  "--trace", "0", "--rehearse"])
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
