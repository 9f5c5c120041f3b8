"""The program's stages and spans in a trace: the ``tf_op`` reader, the
one-pass self-time reduction on synthetic nested planes and on traces
recorded on the chip, and the readers of the new per-layer metrics."""
import gzip
import os
import time
from types import SimpleNamespace as NS

import pytest

from bench import scopes, trace
from bench.metrics import _stages

DATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "data")
TRAD = os.path.join(DATA, "pn2c-trad-b16.xplane.pb.gz")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


#: op event name -> tf_op, as the program's scopes name them
TF_OPS = {
    "%conditional.1 = (s32[]) conditional()":
        "jit(pcn_step)/vmap(pcn.sample)/cond",
    "%fusion.1 = f32[8] fusion()":
        "jit(pcn_step)/vmap(pcn.sample)/vmap(jit(farthest_point_sampling))"
        "/while/body/sub",
    "%sort.2 = f32[8] sort()":
        "jit(pcn_step)/vmap(pcn.neighbors)/vmap(jit(knn_bruteforce))/top_k",
    "%fusion.3 = f32[8] fusion()": "jit(pcn_step)/pcn.head/dot_general",
    "%fusion.4 = f32[8] fusion()": "jit(pcn_step)/add",
    "%fusion.5 = f32[8] fusion()":
        "jit(pcn_step)/pcn.sample/x;jit(pcn_step)/vmap(pcn.islandize)/y",
}


def planes(serve=()):
    ops = [
        # a loop without a tf_op inside a scoped op, around its body ops
        ev("%conditional.1 = (s32[]) conditional()", 100, 300),
        ev("%while.1 = (s32[]) while()", 100, 290),
        ev("%fusion.1 = f32[8] fusion()", 110, 50),
        ev("%copy.9 = f32[8] copy()", 200, 40),         # no tf_op: inherits
        ev("%fusion.1 = f32[8] fusion()", 300, 80),
        ev("%sort.2 = f32[8] sort()", 450, 100),
        ev("%gather_mlp.3 = f32[8] custom-call()", 600, 100),
        ev("%copy.7 = f32[8] copy()", 720, 30),         # top level, no tf_op
        ev("%fusion.4 = f32[8] fusion()", 760, 20),     # outside every scope
        ev("%fusion.3 = f32[8] fusion()", 800, 50),
        ev("%fusion.5 = f32[8] fusion()", 900, 40),     # fused: last scope
    ]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_pcn_step", 100, 900)]),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev("bench.window", 50, 950)]),
        NS(name="pcn-serve_0", events=[ev(n, s, d, seq=0)
                                       for n, s, d in serve])])
    return [device, host]


def test_innermost_event_wins_and_loops_inherit():
    r = scopes.reduce_stages(planes(), TF_OPS)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(950 * ns)
    assert r["stage_s"] == pytest.approx({
        # the conditional's own 10, the loop's own 290 - 50 - 40 - 80,
        # the loop's body ops
        "sample": 300 * ns,
        "neighbors": 100 * ns,
        "head": 50 * ns,
        "islandize": 40 * ns,
        "unscoped": 50 * ns,           # %copy.7 and %fusion.4
    })
    assert r["kernel_s"] == pytest.approx({"gather_mlp": 100 * ns})
    assert r["scoped"] is True
    assert r["serve_s"] == {} and r["idle_in_serve_host_s"] is None


def test_stages_unscoped_and_kernels_sum_to_the_busy_time():
    p = planes()
    r = scopes.reduce_stages(p, TF_OPS)
    base = trace.reduce_planes(p)
    assert r["busy_s"] == pytest.approx(base["busy_s"])
    assert sum(r["stage_s"].values()) == pytest.approx(base["nonkernel_s"])
    assert r["kernel_s"] == pytest.approx(base["kernel_s"])


def test_an_overlap_that_is_no_nesting_goes_to_the_later_event():
    p = planes()
    ops = p[0].lines[1].events
    ops.append(ev("%fusion.3 = f32[8] fusion()", 530, 40))  # over the sort
    r = scopes.reduce_stages(p, TF_OPS)
    ns = 1e-9
    assert r["stage_s"]["neighbors"] == pytest.approx(80 * ns)
    assert r["stage_s"]["head"] == pytest.approx(90 * ns)
    assert r["busy_s"] == pytest.approx(trace.reduce_planes(p)["busy_s"])


def test_a_call_without_scopes_reads_all_as_unscoped():
    r = scopes.reduce_stages(planes(), {})
    assert set(r["stage_s"]) == {"unscoped"}
    assert r["scoped"] is False


def test_device_idle_under_the_servers_host_spans():
    # device idle inside the window: [50,100] [400,450] [550,600]
    # [700,720] [750,760] [780,800] [850,900] [940,1000]
    serve = [("serve.pad", 60, 30),          # 30 idle
             ("serve.device", 380, 100),     # not host work
             ("serve.readback", 540, 20),    # with the next: 20 idle
             ("serve.complete", 545, 25),    # overlaps the readback
             ("serve.fire", 850, 200)]       # 50 + 60, clipped at 1000
    r = scopes.reduce_stages(planes(serve), TF_OPS)
    ns = 1e-9
    assert r["idle_in_serve_host_s"] == pytest.approx(160 * ns)
    assert r["serve_s"]["serve.device"] == pytest.approx(100 * ns)
    assert r["serve_s"]["serve.fire"] == pytest.approx(150 * ns)


def test_missing_window_or_device_line_is_an_error():
    p = planes()
    p[1].lines[0].events.clear()
    with pytest.raises(ValueError, match="bench.window"):
        scopes.reduce_stages(p, TF_OPS)
    p = planes()
    p[0].lines.pop(1)
    with pytest.raises(ValueError, match="XLA Ops"):
        scopes.reduce_stages(p, TF_OPS)


def test_stage_of_takes_the_last_stage_of_a_name_stack():
    assert scopes.stage_of(TF_OPS["%fusion.5 = f32[8] fusion()"]) \
        == "islandize"
    assert scopes.stage_of("jit(pcn_step)/add") is None
    assert scopes.stage_of(None) is None


def test_tf_ops_of_a_trace_recorded_on_the_chip():
    """The pn2c-trad-b16 trace recorded before the program had scopes:
    the reader finds the name stacks XLA left in the metadata."""
    with open(TRAD, "rb") as f:
        raw = gzip.decompress(f.read())
    t0 = time.perf_counter()
    ops = scopes.read_tf_ops(raw)
    assert time.perf_counter() - t0 < 1.0
    (sort,) = [v for k, v in ops.items() if k.startswith("%sort.3 ")]
    assert "knn_bruteforce" in sort
    assert not [k for k in ops if k.startswith("%while.33 ")]
    assert len(ops) == 86
    # the step was an unnamed jit then; input copies carry the argument
    assert {v.split("/")[0] for v in ops.values()} \
        == {"jit(<unknown>)", "batch[0]:", "batch[1]:"}


def test_the_recorded_trace_reduces_as_the_benchmark_does():
    from jax.profiler import ProfileData
    with open(TRAD, "rb") as f:
        raw = gzip.decompress(f.read())
    profile = ProfileData.from_serialized_xspace(raw)
    r = scopes.reduce_stages(profile.planes, scopes.read_tf_ops(raw))
    base = trace.reduce_planes(profile.planes)
    assert r["busy_s"] == pytest.approx(base["busy_s"], rel=1e-9)
    assert r["stage_s"] == pytest.approx({"unscoped": base["nonkernel_s"]},
                                         rel=1e-9)
    assert r["scoped"] is False


LPCN = os.path.join(DATA, "pn2c-lpcn-b2.xplane.pb.gz")


def test_a_scoped_lpcn_trace_recorded_on_the_chip():
    """Three steps of the lpcn program on one TPU v5e: pn2c-lpcn-b16's
    configuration and mix cut to batches of 2, one in flight, in a
    ``bench.window`` span; ``bench.run``'s profiler options."""
    from jax.profiler import ProfileData
    with open(LPCN, "rb") as f:
        raw = gzip.decompress(f.read())
    profile = ProfileData.from_serialized_xspace(raw)
    r = scopes.reduce_stages(profile.planes, scopes.read_tf_ops(raw))
    base = trace.reduce_planes(profile.planes)
    # pinned: the reduction of this file is plain arithmetic
    assert r["stage_s"] == pytest.approx({
        "islandize": 0.101302125, "schedule": 0.016180294,
        "overflow": 0.00683569, "sample": 0.004428672,
        "reuse_inputs": 0.003919464, "dense_inputs": 0.001813588,
        "neighbors": 0.000653739, "head": 0.000050953,
        "unscoped": 0.006884432}, rel=1e-9)
    assert r["kernel_s"] == pytest.approx(base["kernel_s"], rel=1e-9)
    assert base["kernel_calls"] == {"gather_mlp": 6, "hub_reuse": 6}
    assert r["busy_s"] == pytest.approx(base["busy_s"], rel=1e-12)
    assert sum(r["stage_s"].values()) == pytest.approx(base["nonkernel_s"],
                                                       rel=1e-9)
    assert r["scoped"] and r["idle_in_serve_host_s"] is None
    ops = scopes.read_tf_ops(raw)
    assert {v.split("/")[0] for v in ops.values()
            if "/" in v} == {"jit(pcn_step)"}


def _ctx(tmp_path, monkeypatch, p, tf_ops, clouds=4):
    """A reader's context over a trace of ``p`` written where a run
    writes it."""
    monkeypatch.setattr(_stages, "TRACES", str(tmp_path))
    path = tmp_path / "cell" / "run.xplane.pb"
    path.parent.mkdir(exist_ok=True)
    path.write_bytes(b"")
    monkeypatch.setattr(scopes, "reduce_file",
                        lambda _: scopes.reduce_stages(p, tf_ops))
    _stages._reduce.cache_clear()
    return {"trace": trace.reduce_planes(p), "clouds": clouds}


def _reader(name):
    from bench import run
    return run.metric_reader(name)


STAGES = ["sample", "neighbors", "islandize", "schedule", "reuse_inputs",
          "overflow", "dense_inputs", "head", "unscoped"]


@pytest.mark.parametrize("stage", STAGES)
def test_stage_readers(tmp_path, monkeypatch, stage):
    p = planes()
    ctx = _ctx(tmp_path, monkeypatch, p, TF_OPS)
    got = _reader(f"stage_ms.{stage}")(ctx)
    want = {"sample": 300, "neighbors": 100, "head": 50, "islandize": 40,
            "unscoped": 50}.get(stage)
    if want is None:                      # no such stage in the trace
        assert got is None
    else:
        assert got == pytest.approx(1e3 * want * 1e-9 / 4)


@pytest.mark.parametrize("stage", STAGES)
def test_stage_readers_read_nothing_without_scopes(tmp_path, monkeypatch,
                                                   stage):
    ctx = _ctx(tmp_path, monkeypatch, planes(), {})
    assert _reader(f"stage_ms.{stage}")(ctx) is None


def test_stage_readers_sum_to_nonkernel_device_ms(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch, planes(), TF_OPS)
    total = sum(_reader(f"stage_ms.{s}")(ctx) or 0.0 for s in STAGES)
    assert total == pytest.approx(_reader("nonkernel_device_ms")(ctx))


def test_idle_in_host_reader(tmp_path, monkeypatch):
    serve = [("serve.pad", 60, 30), ("serve.device", 380, 100)]
    ctx = _ctx(tmp_path, monkeypatch, planes(serve), TF_OPS)
    idle = _reader("serve.idle_in_host_pct")(ctx)
    assert idle == pytest.approx(100.0 * 30 / 950)
    assert idle <= _reader("device_idle_pct.serve")(ctx)
    ctx = _ctx(tmp_path, monkeypatch, planes(), TF_OPS)
    assert _reader("serve.idle_in_host_pct")(ctx) is None


def test_readers_read_nothing_without_the_runs_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(_stages, "TRACES", str(tmp_path / "none"))
    _stages._reduce.cache_clear()
    ctx = {"trace": trace.reduce_planes(planes()), "clouds": 4}
    assert _reader("stage_ms.sample")(ctx) is None
    assert _reader("serve.idle_in_host_pct")(ctx) is None
    # another run's trace: its window is not this run's
    ctx = _ctx(tmp_path, monkeypatch, planes(), TF_OPS)
    ctx["trace"] = dict(ctx["trace"], window_s=1.0)
    assert _reader("stage_ms.sample")(ctx) is None
