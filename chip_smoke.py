"""Chip smoke test: the L-PCN main path, end to end, on one TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chip   # the mesh-sharded path, four chips

Drives ``engine.apply`` -> batched Pallas FC kernels -> ``PCNServer`` at
the published width of PointNet++ SSG classification (``POINTNET2_C``,
ModelNet40's 1,024 points) with seeded random weights and seeded
synthetic clouds, and checks what comes out against the repo's jnp
reference backend on the same chip.  Phases:

  (a) device   the default device is a TPU, else exit non-zero at once
  (b) engine   lpcn and traditional forwards at B=8, N=1024: the compiled
               program holds the Mosaic kernels (``tpu_custom_call``),
               logits are finite and agree with ``fc_backend="reference"``
               run at "highest" matmul precision
  (c) serving  ``PCNServer`` (buckets 512/1024, batch 8, no fallback)
               replays a seeded 32-request trace: every request answered,
               none degraded, failed or shed; two responses agree with
               ``engine.apply_single`` on the reference backend
  (d) --four-chip only: ``PCNEngine(mesh=data_mesh(4))`` at B=16 equals
               the single-device engine within the sharded contract
               (1e-5) and its output is spread over the four devices

Times printed here are the smoke's own (one process, a few steps, cold
or warm compile cache) and are not benchmark results.  The last line of
standard output is the JSON verdict; any failed phase exits non-zero
before printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))

B = 8                          # engine phase batch of ModelNet40 clouds
                               # (1,024 points each)
B_MESH = 16                    # four-chip phase batch (4 clouds per chip)
N_REQUESTS = 32
BUCKETS = (512, 1024)
SEED = 0                       # weights, clouds, keys and the trace
# The pallas path runs XLA's default TPU matmul precision (bf16 passes)
# in its jnp layers; the reference runs at "highest".  Agreement is the
# worst |logit difference| over the largest reference |logit|.
REL_TOL = 5e-2
SHARDED_TOL = 1e-5             # tests/test_distributed.py's contract


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def require_tpu(min_devices: int):
    """Phase (a): refuse anything but a TPU before touching the model."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (default device platform is "
                 f"{devs[0].platform!r}); this smoke runs on the chip only")
    if len(devs) < min_devices:
        sys.exit(f"chip_smoke: needs {min_devices} TPU devices, found "
                 f"{len(devs)}")
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    return devs


def rel_err(got, ref) -> tuple[float, float]:
    import numpy as np
    got, ref = np.asarray(got), np.asarray(ref)
    worst = float(np.max(np.abs(got - ref)))
    return worst, worst / max(float(np.max(np.abs(ref))), 1e-30)


def make_batch(n_clouds: int, seed: int):
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import make_dataset
    from repro.engine import Batch
    clouds, _, _ = make_dataset("modelnet40", n_clouds, seed=seed)
    return Batch.make(jnp.asarray(clouds), key=jax.random.PRNGKey(seed))


def kernel_names(lowered_text: str) -> set[str]:
    return {name for name in ("gather_mlp", "hub_reuse")
            if f'kernel_name = "{name}"' in lowered_text}


def engine_phase(spec, params, batch) -> None:
    """Phase (b): both modes through the batched Pallas FC kernels."""
    import jax
    import numpy as np

    from repro import engine
    from repro.kernels import plans

    for mode, want in (("lpcn", {"gather_mlp", "hub_reuse"}),
                       ("traditional", {"gather_mlp"})):
        run = jax.jit(partial(engine.apply, spec=spec, mode=mode,
                              fc_backend="pallas"))
        t0 = time.perf_counter()
        with plans.capture() as used:
            lowered = run.lower(params, batch)
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        for e in used:
            tile = e["plan"].get("ts", e["plan"].get("th"))
            log(f"{mode}: plan {e['kernel']} {e['dims']} -> "
                f"provenance={e['plan']['provenance']} tile={tile} "
                f"lanes={e['plan']['lanes']}")
        check(all(e["plan"]["provenance"] == "heuristic" for e in used),
              f"{mode}: a tile plan came from outside the committed code")
        n_custom = compiled.as_text().count("tpu_custom_call")
        names = kernel_names(lowered.as_text())
        log(f"{mode}: compiled in {compile_s:.2f}s; tpu_custom_call x"
            f"{n_custom}; kernels {sorted(names)}")
        check(n_custom > 0 and names == want,
              f"{mode}: expected Mosaic kernels {sorted(want)} in the "
              f"executable, found {sorted(names)} ({n_custom} custom calls)")

        logits = compiled(params, batch)
        jax.block_until_ready(logits)
        check(logits.shape == (batch.batch_size, spec.n_classes),
              f"{mode}: logits shape {logits.shape}")
        check(bool(np.isfinite(np.asarray(logits)).all()),
              f"{mode}: non-finite logits")
        step_ms = []
        for _ in range(5):
            t1 = time.perf_counter()
            jax.block_until_ready(compiled(params, batch))
            step_ms.append(1e3 * (time.perf_counter() - t1))
        log(f"{mode}: smoke timing, not a benchmark: steps ms "
            f"{[round(t, 3) for t in step_ms]}")

        with jax.default_matmul_precision("highest"):
            ref = jax.jit(partial(engine.apply, spec=spec, mode=mode,
                                  fc_backend="reference"))(params, batch)
            jax.block_until_ready(ref)
        worst, rel = rel_err(logits, ref)
        log(f"{mode}: pallas vs highest-precision reference: worst |diff| "
            f"{worst:.3e}, relative {rel:.3e} (tolerance {REL_TOL:.0e})")
        check(rel <= REL_TOL, f"{mode}: pallas logits disagree with the "
                              f"reference ({rel:.3e} > {REL_TOL:.0e})")


def serving_phase(spec, params, seed: int) -> None:
    """Phase (c): the continuous-batching server on the lpcn kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import engine, serve
    from repro.data.synthetic import make_cloud

    eng = engine.PCNEngine(spec, mode="lpcn", fc_backend="pallas")
    buckets = serve.BucketSet.make(list(BUCKETS), batch=B)
    t0 = time.perf_counter()
    server = serve.PCNServer(eng, params, buckets, timeout_s=0.01,
                             fallback=None, seed=seed)
    log(f"serving: warmed {len(buckets)} buckets in "
        f"{time.perf_counter() - t0:.2f}s")
    try:
        # median 0.7 x the largest bucket: about a fifth of the clouds
        # fit the small bucket, so both buckets serve traffic
        events = serve.synthetic_trace(
            n_requests=N_REQUESTS, rate_hz=200.0,
            n_median=int(0.7 * max(BUCKETS)), n_max=max(BUCKETS), seed=seed)
        rng = np.random.default_rng(seed)
        clouds: list = []

        def make_request(n, i):
            clouds.append(make_cloud(rng, n))
            return clouds[-1], None

        rids = serve.replay(server, events, make_request)
    finally:
        server.close()
    admitted = [r for r in rids if r is not None]
    answered = [r for r in admitted
                if server.ready(r) and not server.failed(r)]
    rep = server.report()
    faults = rep["faults"]
    log(f"serving: {len(answered)}/{len(events)} answered, "
        f"{len(events) - len(admitted)} shed, "
        f"{rep['dispatches']} dispatches over "
        f"{sorted(rep['per_bucket'])}, faults {faults}")
    check(len(answered) == len(events) == len(admitted),
          "serving: not every request was answered")
    check(all(v == 0 for v in faults.values()),
          f"serving: degraded/failed/shed requests: {faults}")
    check(len(rep["per_bucket"]) == len(BUCKETS),
          f"serving: the trace did not reach every bucket "
          f"{sorted(rep['per_bucket'])}")

    out = {r: server.take(r) for r in answered}
    sizes = [c.shape[0] for c in clouds]
    picks = (int(np.argmin(sizes)), int(np.argmax(sizes)))
    single = jax.jit(partial(engine.apply_single, spec=spec, mode="lpcn",
                             fc_backend="reference"))
    for i in picks:
        rid = rids[i]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), rid)
        xyz = jnp.asarray(clouds[i])
        with jax.default_matmul_precision("highest"):
            ref, _ = single(params, xyz, xyz, key)
            ref = np.asarray(ref)
        worst, rel = rel_err(out[rid], ref)
        log(f"serving: request {rid} ({sizes[i]} points) vs apply_single "
            f"reference: worst |diff| {worst:.3e}, relative {rel:.3e} "
            f"(tolerance {REL_TOL:.0e})")
        check(rel <= REL_TOL,
              f"serving: request {rid} disagrees with apply_single")


def four_chip_phase(spec, params, seed: int) -> None:
    """Phase (d): the mesh-sharded engine over four chips."""
    import jax
    import numpy as np

    from repro import engine
    from repro.launch.mesh import data_mesh

    batch = make_batch(B_MESH, seed)
    single = engine.PCNEngine(spec, mode="lpcn", fc_backend="pallas")
    sharded = engine.PCNEngine(spec, mode="lpcn", fc_backend="pallas",
                               mesh=data_mesh(4))
    t0 = time.perf_counter()
    ref = single.apply(params, batch)
    jax.block_until_ready(ref)
    t1 = time.perf_counter()
    got = sharded.apply(params, batch)
    jax.block_until_ready(got)
    t2 = time.perf_counter()
    log(f"four-chip: first calls (compile included) single {t1 - t0:.2f}s, "
        f"sharded {t2 - t1:.2f}s")
    devs = {s.device for s in got.addressable_shards}
    rows = sorted(s.data.shape[0] for s in got.addressable_shards)
    log(f"four-chip: output over {len(devs)} devices, rows per shard "
        f"{rows}")
    check(len(devs) == 4 and rows == [B_MESH // 4] * 4,
          f"four-chip: output is not split over 4 devices ({rows})")
    worst = float(np.max(np.abs(np.asarray(got) - np.asarray(ref))))
    log(f"four-chip: sharded vs single device: worst |diff| {worst:.3e} "
        f"(contract: allclose rtol=atol={SHARDED_TOL:.0e})")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=SHARDED_TOL, atol=SHARDED_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the mesh-sharded phase on four chips")
    args = ap.parse_args(argv)

    devs = require_tpu(4 if args.four_chip else 1)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    import jax

    from repro import engine
    from repro.kernels import plans
    from repro.models.pointnet2 import POINTNET2_C

    plans.configure(None)          # heuristic tile plans: committed code only
    spec = POINTNET2_C
    params = engine.init(jax.random.PRNGKey(SEED), spec)
    t0 = time.perf_counter()
    if args.four_chip:
        four_chip_phase(spec, params, SEED)
    else:
        engine_phase(spec, params, make_batch(B, SEED))
        serving_phase(spec, params, SEED)
    log(f"all phases passed in {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
