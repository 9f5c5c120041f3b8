"""BENCHMARK.json keeps to its contract, and the harness finds every
configuration, traffic mix, limit file and per-layer metric by name, so
that a later cell or metric is added by adding files and entries."""
import json
import os
import re
import shutil

import pytest

from bench import families, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        names.add(c["name"])
    used = {w["config"] for w in bench["workloads"]}
    assert used == names
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for w in bench["workloads"]:
        mine = [m for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        reported = {m["name"] for m in mine}
        layer = [m for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer and all(m["moves"] in reported for m in layer)


def test_every_named_file_loads(bench):
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell["config"]["name"] == w["config"]
        assert "logit_gap" in cell["limits"]
        assert hasattr(run.driver_class(cell["traffic"]), "window")
        assert hasattr(families.of(cell["config"]), "reference")
    for m in bench["per_layer"]:
        assert callable(run.metric_reader(m["name"]))


def test_a_new_cell_and_metric_are_found_by_name(tmp_path, monkeypatch,
                                                  bench):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(run.ROOT, "bench"), root / "bench")
    bench = json.loads(json.dumps(bench))
    bench["configs"].append({"name": "pointnet2_x", "source": "s",
                             "file": "bench/configs/pointnet2_x.json",
                             "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "new-cell", "config": "pointnet2_x",
                               "traffic": "new_mix", "chips": 1, "why": "w"})
    bench["per_layer"].append({"name": "new.metric", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "setup_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cfg = json.loads((root / "bench/configs/pointnet2_c.json").read_text())
    cfg["name"] = "pointnet2_x"
    (root / "bench/configs/pointnet2_x.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/new_mix.json").write_text(json.dumps(
        {"driver": "offline", "batch": 4}))
    (root / "bench/limits/new-cell.json").write_text(json.dumps(
        {"logit_gap": {"limit": 1.0}}))
    (root / "bench/metrics/new.metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    monkeypatch.setattr(run, "ROOT", str(root))
    monkeypatch.setattr(run, "BENCH", str(root / "bench"))
    cell = run.load_cell("new-cell")
    assert cell["config"]["name"] == "pointnet2_x"
    assert cell["traffic"]["batch"] == 4
    assert [m["name"] for m in cell["per_layer"]] == ["new.metric"]
    assert run.metric_reader("new.metric")({}) == 42.0
    with pytest.raises(SystemExit):
        run.load_cell("no-such-cell")
