#!/usr/bin/env bash
# Tier-1 verification + a ~30s engine smoke benchmark + a padding-
# equivalence smoke (the ragged-batch contract, see tests/test_padding.py
# for the full oracle) + serving smokes (ragged trace, chaos fault
# injection, overload shed — see tests/test_serve.py) + a mesh-sharded
# engine smoke (8 forced host devices, subprocess — see
# tests/test_distributed.py for the full equivalence suite).
#
#   bash scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== repro.dist collection check =="
# the four modules that used to skip via importorskip("repro.dist") must
# now collect real tests (PR 5 reconstructed the subsystem)
collected=$(python -m pytest --collect-only -q tests/test_substrate.py \
    tests/test_distributed.py tests/test_lm_smoke.py \
    tests/test_train_ckpt.py 2>/dev/null | tail -1 || true)
echo "$collected"
# must be a positive count ("no tests collected" / errors fail here)
if ! echo "$collected" | grep -qE '^[1-9][0-9]* tests? collected'; then
  echo "formerly-skipped tier-1 modules no longer collect"; exit 1
fi

echo "== padding-equivalence smoke =="
python - <<'EOF'
import numpy as np, jax, jax.numpy as jnp
from dataclasses import replace
from repro import engine
from repro.data.synthetic import make_cloud
from repro.engine import Batch, BlockSpec
from repro.models import pointnet2

spec = replace(pointnet2.POINTNET2_C, blocks=(
    BlockSpec(48, 8, (16, 32)), BlockSpec(16, 8, (32, 48))))
params = engine.init(jax.random.PRNGKey(0), spec)
rng = np.random.default_rng(0)
clouds = [np.asarray(make_cloud(rng, n), np.float32) for n in (96, 72, 60)]
keys = jax.random.split(jax.random.PRNGKey(1), 3)
batch = Batch.from_clouds(clouds, key=keys)
for mode in ("traditional", "lpcn"):
    out = engine.apply(params, batch, spec=spec, mode=mode)
    for i, c in enumerate(clouds):
        ref, _ = engine.apply_single(params, jnp.asarray(c), jnp.asarray(c),
                                     keys[i], spec=spec, mode=mode)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
print("padding smoke ok: padded ragged batch == per-cloud unpadded "
      "(traditional + lpcn)")
EOF

echo "== batched-kernel smoke (interpret mode) =="
python - <<'EOF'
import numpy as np, jax, jax.numpy as jnp
from functools import partial
from dataclasses import replace
from repro import engine
from repro.data.synthetic import make_cloud
from repro.engine import Batch, BlockSpec
from repro.models import pointnet2

spec = replace(pointnet2.POINTNET2_C, blocks=(
    BlockSpec(48, 8, (16, 32)), BlockSpec(16, 8, (32, 48))))
params = engine.init(jax.random.PRNGKey(0), spec)
rng = np.random.default_rng(0)
xyz = jnp.asarray(np.stack([make_cloud(rng, 96) for _ in range(3)]))
batch = Batch.make(xyz, key=jax.random.PRNGKey(1),
                   n_valid=jnp.asarray([96, 70, 50], jnp.int32))
ref = engine.apply(params, batch, spec=spec, mode="lpcn",
                   fc_backend="reference")
pal = engine.apply(params, batch, spec=spec, mode="lpcn",
                   fc_backend="pallas")
np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                           rtol=1e-4, atol=1e-4)

# one pallas_call per FC call site, batch folded into the grid (the
# jaxpr walker is the repro.analysis one — shared with
# tests/test_batched_fc.py and the kernel linter: one implementation)
from repro.analysis import count_pallas_calls

jx = jax.make_jaxpr(partial(engine.apply, spec=spec, mode="lpcn",
                            fc_backend="pallas"))(params, batch)
grids = []
n = count_pallas_calls(jx.jaxpr, grids)
assert n == 2 * len(spec.blocks), (n, grids)
assert all(g[0] == 3 for g in grids), grids
print(f"batched-kernel smoke ok: pallas==reference on a ragged batch, "
      f"{n} pallas_calls for {len(spec.blocks)} blocks, grids={grids}")
EOF

echo "== static analysis gate (repro.analysis --strict) =="
# kernel / recompile / ragged-masking / repo lint over the full
# 4-model x 2-mode x 2-backend matrix + serve/dist entry points;
# unsuppressed error-severity findings fail CI.  The JSON report lands
# in results/ and is uploaded with the benchmark artifacts.
python -m repro.analysis --strict --json results/analysis_report.json
python - <<'EOF'
import json
rep = json.load(open("results/analysis_report.json"))
assert rep["summary"]["strict_ok"], rep["summary"]
assert rep["kernel_sites"], "analysis saw no pallas_call sites"
for row in rep["kernel_sites"]:
    assert row["footprint_bytes"] > 0 and len(row["grid"]) == 2, row
print(f"analysis gate ok: {len(rep['kernel_sites'])} kernel sites, "
      f"{rep['summary']['findings']} findings "
      f"({rep['summary']['suppressed']} suppressed, 0 errors)")
EOF

echo "== engine smoke benchmark =="
python -m benchmarks.run --quick --only engine --out results/engine_smoke.json
python - <<'EOF'
import json
rows = json.load(open("results/engine_smoke.json"))
assert rows, "engine smoke produced no rows"
for r in rows:
    assert "backend" in r and "batch" in r, r
ragged = [r for r in rows if r.get("ragged")]
assert ragged, "engine smoke missing the ragged-batch configuration"
for r in ragged:
    assert "n_valid" in r and "sizes" in r["n_valid"], r
print(f"engine smoke ok: {len(rows)} rows "
      f"(backends: {sorted({r['backend'] for r in rows})}, "
      f"{len(ragged)} ragged)")
EOF

echo "== tile-plan autotune smoke (tiny budget, 1 model x 1 shape) =="
# a from-scratch tune run: the cache file must be written, every
# promoted winner must carry provenance "autotuned" and re-pass the
# K001-K005 kernel lint at its own budget (what repro.analysis --strict
# holds traced calls to).  The workflow uploads results/tile_plans.json
# with the other benchmark artifacts.
rm -f results/tile_plans.json
python -m repro.launch.autotune --models pointnet2_c --reduced \
    --points 96 --batches 2 --budget 4 --reps 2 \
    --out results/tile_plans.json
python - <<'EOF'
import json
from repro.kernels import plans
from repro.launch import autotune

raw = json.load(open("results/tile_plans.json"))
assert raw["version"] == plans.VERSION, raw
assert raw["plans"], "autotune smoke promoted no plans"
variants = 0
for key, entry in raw["plans"].items():
    kernel, dimstr = key.split("|", 1)
    assert entry["provenance"] == "autotuned", (key, entry)
    assert plans.entry_error(kernel, entry) is None, (key, entry)
    if entry.get("variant") == "vmap":
        # a cell where the per-cloud vmap dispatch out-measured every
        # grid candidate: no grid knobs to lint (the per-cloud kernel
        # is covered by the analysis matrix)
        variants += 1
        continue
    dims = dict(kv.split("=") for kv in dimstr.split(","))
    dims = {k: int(v) for k, v in dims.items()}
    knobs = {"tile": entry[plans.TILE_FIELD[kernel]],
             "lanes": entry["lanes"],
             "vmem_budget_mb": entry["vmem_budget_mb"],
             "dimension_semantics": tuple(entry["dimension_semantics"])}
    findings = autotune.lint_knobs(kernel, dims, knobs)
    assert not findings, (key, [f.rule for f in findings])
print(f"autotune smoke ok: {len(raw['plans'])} plans promoted "
      f"({variants} vmap variants), all provenance=autotuned and "
      f"grid winners K001-K005 clean")
EOF

echo "== fc_kernel A/B benchmark (vmap vs heuristic vs autotuned) =="
python -m benchmarks.run --quick --only fc_kernel \
    --out results/fc_kernel_smoke.json
python - <<'EOF'
import json
rows = json.load(open("results/fc_kernel_smoke.json"))
batched = [r for r in rows if r.get("dispatch") == "batched_grid"]
vmap = [r for r in rows if r.get("dispatch") == "vmap"]
assert batched and vmap, "fc_kernel smoke missing an A/B side"
for r in batched:
    assert r["per_cloud_dispatches"] == 1, r
kern = [r for r in batched if "tile" in r]
assert kern, "fc_kernel smoke missing kernel-level tile plans"
for r in kern:
    assert "grid" in r and len(r["grid"]) == 2, r
    # provenance is observed from the plan the trace actually resolved
    expect = "autotuned" if "autotuned" in r["name"] else "heuristic"
    assert r["tile_provenance"] == expect, r
tuned = [r for r in kern if r["tile_provenance"] == "autotuned"]
assert tuned, "fc_kernel smoke has no autotuned rows"
curve = [r for r in rows if "speedup_curve" in r["name"]]
assert curve and all(r["curve"] for r in curve), \
    "fc_kernel smoke missing the speedup-vs-B curve rows"
eng_tuned = [r for r in rows if r.get("backend") == "pallas_autotuned"]
assert eng_tuned and all(r["tile_provenance"] == ["autotuned"]
                         for r in eng_tuned), eng_tuned
print(f"fc_kernel smoke ok: {len(rows)} rows "
      f"({len(vmap)} vmap vs {len(batched)} batched-grid, "
      f"{len(tuned)} autotuned kernel rows, "
      f"{len(eng_tuned)} autotuned engine rows)")
EOF

echo "== serve-trace smoke (continuous batching, ragged trace) =="
# a short synthetic ragged trace through launch/serve.py --trace: the
# admission queue / size buckets / timeout dispatcher end to end, with
# the report JSON landing in results/ (uploaded with the other
# benchmark artifacts by the workflow)
python -m repro.launch.serve --arch pointnet2_c --reduced --points 96 \
    --batch 2 --trace 16 --rate 300 --buckets 96,128 --timeout-ms 5 \
    --serve-json results/serve_trace_smoke.json
python - <<'EOF'
import json
rep = json.load(open("results/serve_trace_smoke.json"))
assert rep["requests"] == 16 and rep["answered"] == 16, rep
assert rep["throughput_rps"] > 0, rep
for name, lat in rep["latency_ms"].items():
    assert lat["p50"] <= lat["p95"] <= lat["p99"], (name, lat)
assert 0 <= rep["padding_waste_pct"] < 100, rep
# compile-once per bucket: the trace spans both buckets
assert rep["compile_count"] == len(rep["buckets"]) == 2, rep
print(f"serve smoke ok: {rep['requests']} requests, "
      f"{rep['dispatches']} dispatches "
      f"({rep['partial_batches']} partial), "
      f"e2e p50/p95/p99 = {rep['latency_ms']['e2e']['p50']:.1f}/"
      f"{rep['latency_ms']['e2e']['p95']:.1f}/"
      f"{rep['latency_ms']['e2e']['p99']:.1f} ms, "
      f"waste {rep['padding_waste_pct']:.1f}%")
EOF

echo "== chaos-trace smoke (fault injection, degraded dispatch) =="
# same trace with a deterministic fault plan: step 1 raises inside the
# primary dispatch, step 3 NaN-poisons its output.  Both batches must
# be retried on the reference fallback and every request still
# answered — the hardened-serving acceptance walk, end to end through
# the CLI.  Exit code 0 is part of the contract: injected faults are
# handled, not propagated.
python -m repro.launch.serve --arch pointnet2_c --reduced --points 96 \
    --batch 2 --trace 16 --rate 300 --buckets 96,128 --timeout-ms 5 \
    --faults "fail@1,nan@3" \
    --serve-json results/serve_chaos_smoke.json
python - <<'EOF'
import json
rep = json.load(open("results/serve_chaos_smoke.json"))
assert rep["requests"] == 16 and rep["answered"] == 16, rep
assert rep["failed"] == 0 and rep["shed"] == 0, rep
fl = rep["faults"]
assert fl["degraded_dispatches"] == 2, fl          # both injected steps
assert fl["failed_requests"] == 0, fl
assert len(rep["fault_plan"]["injected"]) == 2, rep["fault_plan"]
assert rep["breakers"], rep                        # breaker state in report
assert all(b["state"] == "closed" for b in rep["breakers"].values()), \
    rep["breakers"]
print(f"chaos smoke ok: {rep['answered']}/{rep['requests']} answered "
      f"despite injected {rep['fault_plan']['injected']}, "
      f"{fl['degraded_dispatches']} degraded dispatches, 0 failed")
EOF

echo "== async dispatch A/B smoke (sync vs in-flight overlap) =="
# the same 16-request chaos burst replayed twice: once with --sync
# (the fire path blocks through execution) and once with up to 4
# batches in flight.  Both modes must answer 16/16 with IDENTICAL
# fault accounting (fault draws happen at fire time in admission
# order either way), monotone percentiles, and async throughput must
# not lose to sync — at 256-point batches the overlap of host padding
# with device compute wins ~1.2x even on one core.  The combined A/B
# lands in results/serve_async_ab_smoke.json.
for mode in sync async; do
  if [ "$mode" = sync ]; then extra="--sync"; else extra="--max-in-flight 4"; fi
  python -m repro.launch.serve --arch pointnet2_c --reduced --points 256 \
      --batch 2 --trace 16 --rate 2000 --buckets 256,384 --timeout-ms 5 \
      --faults "fail@1,nan@3" $extra \
      --serve-json "results/serve_async_ab_${mode}.json"
done
python - <<'EOF'
import json
reps = {m: json.load(open(f"results/serve_async_ab_{m}.json"))
        for m in ("sync", "async")}
for m, rep in reps.items():
    assert rep["dispatch_mode"] == m, (m, rep["dispatch_mode"])
    assert rep["requests"] == 16 and rep["answered"] == 16, (m, rep)
    assert rep["failed"] == 0 and rep["shed"] == 0, (m, rep)
    for name, lat in rep["latency_ms"].items():
        assert lat["p50"] <= lat["p95"] <= lat["p99"], (m, name, lat)
# identical fault accounting: same trace -> same batches -> the
# injected steps hit the same dispatches in both modes
assert reps["sync"]["faults"] == reps["async"]["faults"], \
    (reps["sync"]["faults"], reps["async"]["faults"])
assert (reps["sync"]["fault_plan"]["injected"]
        == reps["async"]["fault_plan"]["injected"]), reps["async"]["fault_plan"]
rps_s = reps["sync"]["throughput_rps"]
rps_a = reps["async"]["throughput_rps"]
assert rps_a >= rps_s, \
    f"async {rps_a:.1f} rps lost to sync {rps_s:.1f} rps"
ov = reps["async"]["overlap"]
assert ov["inflight_depth_max"] <= 4, ov
assert reps["sync"]["overlap"]["inflight_depth_max"] <= 1, reps["sync"]["overlap"]
with open("results/serve_async_ab_smoke.json", "w") as fh:
    json.dump(reps, fh, indent=1)
print(f"async A/B smoke ok: 16/16 both modes, identical fault "
      f"accounting, async {rps_a:.1f} >= sync {rps_s:.1f} rps "
      f"({rps_a / rps_s:.2f}x), overlap {ov['overlap_pct']:.1f}% "
      f"depth<={ov['inflight_depth_max']}")
EOF

echo "== overload smoke (bounded lanes, shed-on-full backpressure) =="
# batch 4 with a 1-deep lane and a long timeout: the burst trace can
# admit only one request; the other 11 must shed with QueueFullError
# at submit (counted, never forever-pending) and the replay still
# completes with exit 0.
python -m repro.launch.serve --arch pointnet2_c --reduced --points 96 \
    --batch 4 --trace 12 --rate 2000 --buckets 96 --timeout-ms 200 \
    --max-queue 1 \
    --serve-json results/serve_overload_smoke.json
python - <<'EOF'
import json
rep = json.load(open("results/serve_overload_smoke.json"))
assert rep["requests"] == 1, rep           # latency stats: admitted only
assert rep["answered"] == 1, rep
assert rep["shed"] == 11, rep
assert rep["faults"]["shed_queue_full"] == 11, rep["faults"]
print(f"overload smoke ok: answered {rep['answered']}, shed "
      f"{rep['shed']} at a 1-deep lane (shed_queue_full="
      f"{rep['faults']['shed_queue_full']})")
EOF

echo "== sharded engine smoke (8 forced host devices, subprocess) =="
# runs in its own python process (like tests/test_distributed.py) so the
# forced fake device count cannot leak into any other step's jax
XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
python - <<'PYEOF'
import numpy as np, jax, jax.numpy as jnp
from dataclasses import replace
from repro import engine
from repro.data.synthetic import make_cloud
from repro.engine import Batch, BlockSpec
from repro.launch.mesh import make_mesh
from repro.models import pointnet2

assert len(jax.devices()) == 8, jax.devices()
mesh = make_mesh((4, 2), ("data", "model"))
spec = replace(pointnet2.POINTNET2_C, blocks=(
    BlockSpec(32, 8, (16, 32)), BlockSpec(16, 8, (32, 48))))
params = engine.init(jax.random.PRNGKey(0), spec)
rng = np.random.default_rng(0)
xyz = jnp.asarray(np.stack([make_cloud(rng, 96) for _ in range(8)]))
batch = Batch.make(xyz, key=jax.random.PRNGKey(1),
                   n_valid=jnp.asarray([96, 70, 50, 96, 33, 80, 60, 90],
                                       jnp.int32))
for mode in ("traditional", "lpcn"):
    ref = engine.apply(params, batch, spec=spec, mode=mode)
    sh = engine.apply(params, batch, spec=spec, mode=mode, mesh=mesh)
    assert "data" in str(sh.sharding), sh.sharding
    np.testing.assert_allclose(np.asarray(sh), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
print("sharded smoke ok: 8-device mesh engine.apply == single-device on a "
      "ragged batch (traditional + lpcn), output sharded over 'data'")
PYEOF

echo "== dist benchmark smoke (sharded vs single-device throughput) =="
JAX_PLATFORMS=cpu python -m benchmarks.run --quick --only dist \
    --out results/dist_smoke.json
python - <<'PYEOF'
import json
rows = json.load(open("results/dist_smoke.json"))
tags = {r["name"].rsplit("_d", 1)[0] for r in rows}
assert {"dist_engine_single_device", "dist_engine_sharded"} <= tags, tags
for r in rows:
    assert "device_count" in r and "clouds_per_s_per_device" in r, r
sharded = [r for r in rows if r["mesh"]]
assert sharded and all(r["mesh"]["data"] == r["device_count"]
                       for r in sharded), sharded
print(f"dist smoke ok: {len(rows)} rows, device_count="
      f"{rows[0]['device_count']}, mesh shapes recorded")
PYEOF
