"""FLOP and byte counts from the configurations, and the peaks table."""
import json
import os

import pytest

from bench import flops, peaks
from bench.families import pointnet2

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_pointnet2_c_model_flops_by_hand():
    # SA1 512*32 * 2*(6*64 + 64*64 + 64*128), SA2 128*64 * 2*(131*128 +
    # 128*128 + 128*256), global 128 * 2*(259*256 + 256*512 + 512*1024),
    # head 2*(1024*512 + 512*256 + 256*40)
    sa1 = 512 * 32 * 2 * (6 * 64 + 64 * 64 + 64 * 128)
    sa2 = 128 * 64 * 2 * (131 * 128 + 128 * 128 + 128 * 256)
    glob = 128 * 2 * (259 * 256 + 256 * 512 + 512 * 1024)
    head = 2 * (1024 * 512 + 512 * 256 + 256 * 40)
    assert sa1 == pytest.approx(0.415e9, rel=1e-2)
    assert sa2 == pytest.approx(1.080e9, rel=1e-2)
    assert glob == pytest.approx(0.185e9, rel=1e-2)
    assert pointnet2.model_flops_per_cloud(cfg("pointnet2_c")) == \
        sa1 + sa2 + glob + head
    assert pointnet2.model_flops_per_cloud(cfg("pointnet2_c")) == \
        pytest.approx(1.68e9, rel=1e-2)


def test_kernel_counts_follow_the_layers_the_kernels_run():
    c = cfg("pointnet2_c")
    g = pointnet2.gather_mlp_calls(c)
    # the first layer of each 3-layer block runs before the kernel
    assert g[0]["flops"] == 512 * 32 * 2 * (64 * 64 + 64 * 128)
    assert g[1]["flops"] == 128 * 64 * 2 * (128 * 128 + 128 * 256)
    h = pointnet2.hub_reuse_calls(c)
    # 16 islands x 64 cached points, 4 islands x 128 cached points
    assert h[0]["flops"] == 16 * 64 * 2 * (64 * 64 + 64 * 128)
    assert h[1]["flops"] == 4 * 128 * 2 * (128 * 128 + 128 * 256)
    # gathered operand, slot mask, result and weights; centred beforehand
    assert g[1]["bytes"] == (128 * 64 * 128 * 4 + 128 * 64 * 4
                             + 128 * 256 * 4
                             + (128 * 128 + 128 + 128 * 256 + 256) * 4)


def test_roofline_takes_the_larger_bound_per_call():
    p = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    calls = [{"flops": 100, "bytes": 1}, {"flops": 1, "bytes": 100}]
    assert flops.roofline_seconds(calls, p) == pytest.approx(1.0 + 10.0)


def test_peaks_table():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")
