"""The reduction from a profiler trace to device metrics."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def planes():
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_apply", 0, 1000)]),
        NS(name="XLA Ops", events=[
            ev("%sort.1 = (f32[16,512]{1,0:T(8,128)}) sort(f32[16,512]"
               "{1,0} %copy.1), dimensions={1}", 50, 100),      # half out
            ev("%gather_mlp.3 = f32[16,512,128]{2,1,0:T(8,128)} "
               "custom-call(%fusion.2)", 200, 100),
            ev("%fusion.2 = s32[221184]{0:T(1024)S(1)} fusion(s32[16,512]"
               "{1,0} %gte.1), kind=kCustom", 250, 100),       # overlaps
            ev("%hub_reuse.4 = f32[16,4,64,256]{3,2,1,0} custom-call()",
               500, 50),
            ev("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p)", 900, 300),
        ])])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 100, 900),
        ev("bench.dispatch", 100, 150),
        ev("bench.wait", 350, 600),
        ev("something.else", 0, 2000),
    ])])
    return [NS(name="/host:metadata", lines=[]), device, host]


def test_busy_union_kernels_and_gaps():
    r = trace.reduce_planes(planes())
    ns = 1e-9
    assert r["window_s"] == pytest.approx(900 * ns)
    # union inside [100, 1000]: [100,150] + [200,350] + [500,550] + [900,1000]
    assert r["busy_s"] == pytest.approx(350 * ns)
    assert r["kernel_s"] == pytest.approx({"gather_mlp": 100 * ns,
                                           "hub_reuse": 50 * ns})
    assert r["kernel_calls"] == {"gather_mlp": 1, "hub_reuse": 1}
    assert r["nonkernel_s"] == pytest.approx(200 * ns)
    # the program ran 1000 ns, 900 of them inside the window
    assert r["module_runs"] == pytest.approx({"jit_apply": 0.9})
    assert r["module_s"] == pytest.approx({"jit_apply": 900 * ns})
    ops = dict((n, v) for n, v in r["breakdown"]["device_ops"])
    assert ops == pytest.approx({
        "%sort.1 sort (f32[16,512])": 50 * ns,
        "%gather_mlp.3 custom-call f32[16,512,128]": 100 * ns,
        "%fusion.2 fusion s32[221184]": 100 * ns,
        "%hub_reuse.4 custom-call f32[16,4,64,256]": 50 * ns,
        "%fusion.9 fusion f32[8]": 100 * ns})
    gaps = r["breakdown"]["idle_gaps"]
    # idle: [150,200] dispatch, [350,500] wait, [550,900] wait
    assert [g[0] for g in gaps] == ["wait", "wait", "dispatch"]
    assert [g[1] for g in gaps] == pytest.approx([350 * ns, 150 * ns,
                                                  50 * ns])


def test_missing_window_or_device_line_is_an_error():
    p = planes()
    p[2].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_planes(p)
    p = planes()
    p[1].lines.pop(1)
    with pytest.raises(ValueError, match="XLA Ops"):
        trace.reduce_planes(p)


def test_a_kernel_is_its_own_call_not_a_consumer_of_its_result():
    k = ("gather_mlp", "hub_reuse")
    assert trace.kernel_of("%gather_mlp.2 = f32[16,512,128]{2,1,0} "
                           "custom-call(f32[16,512,32,128] %pad.59)",
                           k) == "gather_mlp"
    assert trace.kernel_of("%broadcast_select_fusion.2 = f32[16,512,128]"
                           "{2,1,0} fusion(f32[16,512,128]{2,1,0} "
                           "%gather_mlp.2, pred[16,512] %p)", k) is None
    assert trace.kernel_of("%copy-start.48 = (f32[2,512,128]) "
                           "copy-start(%gather_mlp.2)", k) is None


RECORDED = os.path.join(DATA, "pn2c-trad-b16.xplane.pb.gz")


def test_a_trace_recorded_on_the_chip():
    """A 0.05 s traced window of pn2c-trad-b16 on one TPU v5e."""
    import gzip

    from jax.profiler import ProfileData
    with open(RECORDED, "rb") as f:
        planes = ProfileData.from_serialized_xspace(
            gzip.decompress(f.read())).planes
    r = trace.reduce_planes(planes)
    # pinned: the reduction of this file is plain arithmetic
    assert r["window_s"] == pytest.approx(0.071209939, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.068857816, rel=1e-9)
    assert r["kernel_s"]["gather_mlp"] == pytest.approx(0.005475496,
                                                        rel=1e-9)
    assert r["kernel_calls"] == {"gather_mlp": 10}      # 5 steps x 2 blocks
    # five executions of the step, the first begun before the window
    (runs,) = r["module_runs"].values()
    assert runs == pytest.approx(4.907273193, rel=1e-9)
    assert r["breakdown"]["device_ops"][0][0].startswith("%while.33 while")
    assert 0 < r["busy_s"] <= r["window_s"]
    assert set(r["kernel_s"]) == {"gather_mlp"}      # traditional mode
    assert r["kernel_calls"]["gather_mlp"] >= 2
    assert 0 < r["nonkernel_s"] < r["busy_s"]
    assert r["nonkernel_s"] + sum(r["kernel_s"].values()) \
        == pytest.approx(r["busy_s"])
    ops, gaps = r["breakdown"]["device_ops"], r["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(len(n) <= trace.SHORT and " = " not in n for n, _ in ops)
    assert {g for g, _ in gaps} <= {"dispatch", "wait", "none"}
    assert [v for _, v in gaps] == sorted((v for _, v in gaps), reverse=True)
