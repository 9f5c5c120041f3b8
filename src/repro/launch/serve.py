"""Serving driver: batched inference loops runnable on the dev container.

Two families share one CLI, dispatched on ``--arch``:

  * PCN serving (the L-PCN path) — batched point-cloud inference through
    ``repro.engine``: one compiled executable (spec/mode/backend static)
    fed padded (B, N, 3) batches, continuous throughput loop.

        PYTHONPATH=src python -m repro.launch.serve --arch pointnet2_c \
            --batch 4 --points 1024 --mode lpcn --backend reference

    ``--mesh-data N`` serves through the mesh-sharded path instead: an
    (N, 1) ("data", "model") mesh splits each batch N ways (batch must
    divide; on CPU force fake devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  Without it
    the engine takes the single-device fast path and ``repro.dist`` is
    never imported.

  * PCN trace serving — the continuous-batching layer (``repro.serve``):
    replay a synthetic ragged arrival trace (Poisson arrivals at
    ``--rate`` req/s, log-normal cloud sizes with median ``--points``)
    through the admission queue / size buckets / timeout dispatcher and
    report per-request p50/p95/p99 latency, throughput, padding waste
    and the fault counters as JSON.  Composes with ``--mesh-data``
    (bucket batches must divide the mesh) and ``--kernel-kw``
    unchanged.  The hardened-serving knobs ride along: ``--faults``
    injects a deterministic chaos plan into primary dispatches,
    ``--max-queue`` bounds each bucket lane (shed-on-full),
    ``--deadline-ms`` stamps per-request TTLs, ``--fallback`` picks the
    degraded backend ('' disables it).  Dispatch is async by default
    (up to ``--max-in-flight`` batches in flight, admission/padding
    overlapping device compute); ``--sync`` restores the blocking
    dispatcher as the A/B baseline.

        PYTHONPATH=src python -m repro.launch.serve --arch pointnet2_c \
            --trace 64 --rate 200 --buckets 512,1024 --batch 4 \
            --timeout-ms 10 --faults "fail@1,nan@3" \
            --serve-json results/serve_trace.json

  * LM serving — batched prefill + decode loop with continuous-batching
    slots (unchanged behavior).

        PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b \
            --reduced --batch 4 --prompt-len 16 --gen 8
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _pcn_engine(args):
    """Shared PCN setup: spec (optionally reduced), mesh, engine, params."""
    import jax

    from repro import engine
    from repro.models import MODEL_ZOO

    _, spec = MODEL_ZOO[args.arch]
    if args.reduced:
        from dataclasses import replace
        spec = replace(spec, blocks=tuple(
            replace(b, n_centers=min(b.n_centers, max(args.points // 4, 16)),
                    k=min(b.k, 16)) for b in spec.blocks))
    mesh = None
    if args.mesh_data:
        # data_mesh raises an actionable error (how to force CPU devices /
        # lower the request) when the host has fewer devices than asked
        from repro.launch.mesh import data_mesh
        mesh = data_mesh(args.mesh_data)
        if args.batch % args.mesh_data:
            raise SystemExit(
                f"--batch {args.batch} does not divide over a "
                f"{args.mesh_data}-way data mesh; pick a batch that is a "
                f"multiple of --mesh-data")
    kernel_kw = json.loads(args.kernel_kw) if args.kernel_kw else None
    eng = engine.PCNEngine(spec, mode=args.mode, fc_backend=args.backend,
                           kernel_kw=kernel_kw, mesh=mesh)
    return spec, mesh, eng, eng.init(jax.random.PRNGKey(0))


def serve_pcn(args):
    """Batched PCN inference through the engine (one jit, many batches)."""
    import jax
    import jax.numpy as jnp

    from repro import engine
    from repro.data.synthetic import make_cloud

    spec, mesh, eng, params = _pcn_engine(args)

    rng = np.random.default_rng(0)
    f = spec.in_feats

    def make_batch(step: int):
        xyz = np.stack([make_cloud(rng, args.points)
                        for _ in range(args.batch)])
        feats = None
        if f > 3:
            feats = np.concatenate(
                [xyz, rng.uniform(0, 1, (args.batch, args.points, f - 3))
                 .astype(np.float32)], -1)
        return engine.Batch.make(
            jnp.asarray(xyz), None if feats is None else jnp.asarray(feats),
            key=jax.random.PRNGKey(step))

    # compile once (spec/mode/backend are static; shape fixed by the batch)
    t0 = time.perf_counter()
    logits = eng.apply(params, make_batch(0))
    logits.block_until_ready()
    compile_s = time.perf_counter() - t0

    # pre-build batches so the timed loop measures engine throughput, not
    # host-side cloud synthesis.  Each step blocks on its own result:
    # only syncing once at the end would hide per-step latency entirely
    # (the first timed step absorbs the whole queued dispatch backlog),
    # making latency percentiles meaningless — the throughput cost of
    # per-step syncing is the dispatch gap, which is what a serving
    # latency number must include anyway.
    batches = [make_batch(step) for step in range(1, min(args.steps, 4) + 1)]
    from repro.serve import percentile_summary
    step_ms = []
    for step in range(args.steps):
        t1 = time.perf_counter()
        logits = eng.apply(params, batches[step % len(batches)])
        logits.block_until_ready()
        step_ms.append(1e3 * (time.perf_counter() - t1))
    dt = max(sum(step_ms) / 1e3, 1e-9)
    n = args.steps * args.batch
    lat = percentile_summary(step_ms)
    per_dev = "" if mesh is None else (
        f", {n / dt / args.mesh_data:.1f} clouds/s/device over "
        f"{args.mesh_data} devices")
    print(f"{eng}: compiled in {compile_s:.2f}s; served {n} clouds in "
          f"{dt:.2f}s ({n / dt:.1f} clouds/s, batch={args.batch}, "
          f"N={args.points}{per_dev})")
    print(f"per-step latency ms: p50={lat['p50']:.2f} p95={lat['p95']:.2f} "
          f"p99={lat['p99']:.2f} mean={lat['mean']:.2f} max={lat['max']:.2f}")
    print("logits", tuple(logits.shape))
    return logits


def serve_trace(args):
    """Replay a synthetic ragged arrival trace through the
    continuous-batching layer (``repro.serve``) and write the latency /
    throughput / padding-waste / fault report as JSON.

    ``--faults "fail@1,nan@3,slow@5:80"`` injects a deterministic chaos
    schedule into the primary engine callables (the fallback retry path
    stays clean); ``--max-queue`` bounds each bucket lane
    (shed-on-full), ``--deadline-ms`` stamps every request with a TTL
    past which poll sheds it.  Shed requests count in the report's
    ``faults`` section rather than aborting the replay.
    """
    from repro import serve
    from repro.data.synthetic import make_cloud

    spec, mesh, eng, params = _pcn_engine(args)
    if args.buckets:
        sizes = sorted({int(s) for s in args.buckets.split(",")})
        buckets = serve.BucketSet.make(sizes, batch=args.batch)
    else:
        # no explicit sizes: plan quantile buckets from the trace itself
        probe = serve.synthetic_trace(
            n_requests=max(args.trace, 64), rate_hz=args.rate,
            n_median=args.points, sigma=args.size_sigma, seed=args.seed)
        buckets = serve.BucketSet.plan(
            [e.n_points for e in probe], n_buckets=2, batch=args.batch)
    events = serve.synthetic_trace(
        n_requests=args.trace, rate_hz=args.rate, n_median=args.points,
        sigma=args.size_sigma, n_max=buckets.max_points, seed=args.seed)

    faults = serve.FaultPlan.parse(args.faults) if args.faults else None
    t0 = time.perf_counter()
    server = serve.PCNServer(
        eng, params, buckets, timeout_s=args.timeout_ms / 1e3,
        faults=faults,
        max_lane_depth=args.max_queue or None,
        deadline_s=(args.deadline_ms / 1e3) if args.deadline_ms else None,
        fallback=args.fallback or None,
        max_in_flight=args.max_in_flight, sync=args.sync)
    warmup_s = time.perf_counter() - t0

    rng = np.random.default_rng(args.seed)
    f = spec.in_feats

    def make_request(n, i):
        xyz = np.asarray(make_cloud(rng, n), np.float32)
        feats = None if f <= 3 else np.concatenate(
            [xyz, rng.uniform(0, 1, (n, f - 3)).astype(np.float32)], -1)
        return xyz, feats

    rids = serve.replay(server, events, make_request)
    server.close()                       # join + release the executor
    admitted = [r for r in rids if r is not None]
    answered = sum(server.ready(r) and not server.failed(r)
                   for r in admitted)
    failed = sum(server.failed(r) for r in admitted)
    report = server.report(arch=args.arch, mode=args.mode,
                           backend=args.backend, rate_hz=args.rate,
                           mesh_data=args.mesh_data or None,
                           warmup_s=warmup_s, answered=answered,
                           failed=failed,
                           shed=len(rids) - len(admitted))
    lat = report["latency_ms"]["e2e"]
    fl = report["faults"]
    per_dev = "" if mesh is None else f" over {args.mesh_data} devices"
    dmode = ("sync" if args.sync
             else f"async(max_in_flight={args.max_in_flight})")
    print(f"{eng}: {buckets}, timeout={args.timeout_ms:.1f}ms, {dmode}; "
          f"warmed {len(buckets)} buckets in {warmup_s:.2f}s; answered "
          f"{answered}/{len(rids)} requests{per_dev}")
    ov = report["overlap"]
    print(f"throughput {report['throughput_rps']:.1f} req/s "
          f"(offered {args.rate:.1f}), padding waste "
          f"{report['padding_waste_pct']:.1f}%, dispatches "
          f"{report['dispatches']} ({report['partial_batches']} partial), "
          f"overlap {ov['overlap_pct']:.1f}% "
          f"(depth<={ov['inflight_depth_max']}, "
          f"idle gap {ov['idle_gap_ms']:.1f}ms)")
    print(f"e2e latency ms: p50={lat['p50']:.2f} p95={lat['p95']:.2f} "
          f"p99={lat['p99']:.2f} max={lat['max']:.2f}")
    print(f"faults: degraded={fl['degraded_dispatches']} "
          f"failed={fl['failed_requests']} "
          f"shed_queue_full={fl['shed_queue_full']} "
          f"deadline_miss={fl['deadline_miss']} "
          f"breaker_opened={fl['breaker_opened']}")
    if args.serve_json:
        os.makedirs(os.path.dirname(args.serve_json) or ".", exist_ok=True)
        with open(args.serve_json, "w") as fh:
            json.dump(report, fh, indent=1)
        print(f"report written to {args.serve_json}")
    return report


def serve_lm(args):
    """Batched prefill + decode loop with continuous-batching slots."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.dist import sharding as shd
    from repro.launch.mesh import local_mesh
    from repro.lm import model_zoo as zoo
    from repro.lm import steps as steps_mod

    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = local_mesh()
    rng = np.random.default_rng(0)

    with shd.use_mesh(mesh):
        key = jax.random.PRNGKey(0)
        params = zoo.init(key, cfg)
        frames = None
        if cfg.family == "audio":
            frames = 0.01 * jnp.ones(
                (args.batch, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        cache = zoo.make_cache(cfg, params, args.batch, args.cache_len,
                               frames=frames)
        decode = jax.jit(steps_mod.make_decode_step(cfg),
                         donate_argnums=(2,))

        # "prefill" by teacher-forcing the prompt through decode slots
        # (token-by-token; the batched prefill path is exercised in the
        # dry-run and tests)
        prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len),
                               dtype=np.int32)
        tok = jnp.asarray(prompts[:, 0])
        t0 = time.time()
        for pos in range(args.prompt_len - 1):
            _, _, cache = decode(params, tok, cache, jnp.int32(pos))
            tok = jnp.asarray(prompts[:, pos + 1])
        out = []
        for g in range(args.gen):
            tok, logits, cache = decode(params, tok, cache,
                                        jnp.int32(args.prompt_len + g))
            out.append(np.asarray(tok))
        dt = time.time() - t0
        gen = np.stack(out, 1)
        print(f"generated {gen.shape} tokens in {dt:.2f}s "
              f"({args.batch*args.gen/dt:.1f} tok/s)")
        print(gen)
        return gen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    # LM options
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=64)
    # PCN options
    ap.add_argument("--points", type=int, default=1024)
    ap.add_argument("--mode", default="lpcn",
                    choices=["lpcn", "traditional"])
    ap.add_argument("--backend", default="reference")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--mesh-data", type=int, default=0,
                    help="serve through an (N, 1) data mesh (0 = "
                         "single-device fast path, no repro.dist import)")
    ap.add_argument("--kernel-kw", default=None,
                    help='JSON kernel knob, e.g. \'{"ts": 32}\' '
                         "(passed to PCNEngine(kernel_kw=...))")
    # PCN trace-serving options (--trace N turns the mode on)
    ap.add_argument("--trace", type=int, default=0,
                    help="replay a synthetic ragged trace of N requests "
                         "through the continuous-batching layer")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--size-sigma", type=float, default=0.35,
                    help="log-normal size spread (median = --points)")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated bucket pad sizes, e.g. "
                         "'512,1024' (default: quantile-planned from "
                         "the trace); per-bucket batch is --batch")
    ap.add_argument("--timeout-ms", type=float, default=10.0,
                    help="partial-batch dispatch timeout")
    ap.add_argument("--faults", default=None,
                    help="deterministic fault plan for the primary "
                         "engine path, e.g. 'fail@1,nan@3,slow@5:80' "
                         "(kind@dispatch-step[:arg_ms])")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="per-bucket lane depth bound; submits into a "
                         "full lane are shed (0 = unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline; expired queued requests "
                         "are shed at poll time (0 = none)")
    ap.add_argument("--fallback", default="reference",
                    help="FC backend for the one-shot degraded retry of "
                         "a failed batch ('' disables)")
    ap.add_argument("--max-in-flight", type=int, default=4,
                    help="how many fired batches may be in flight at "
                         "once (async dispatch; admission, host padding "
                         "and device compute overlap across buckets)")
    ap.add_argument("--sync", action="store_true",
                    help="fully-blocking dispatch (the pre-async "
                         "behavior) — the A/B baseline for "
                         "--max-in-flight")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--serve-json", default="results/serve_trace.json",
                    help="where the trace report JSON goes ('' = skip)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.models import MODEL_ZOO
    if args.arch in MODEL_ZOO:
        return serve_trace(args) if args.trace else serve_pcn(args)
    if args.mesh_data:
        raise SystemExit(
            "--mesh-data is the PCN engine's sharded path; the LM path "
            "builds its mesh from the host via launch.mesh.local_mesh() "
            "(force devices with XLA_FLAGS=--xla_force_host_platform_"
            "device_count=N instead)")
    try:
        return serve_lm(args)
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"--arch {args.arch!r} is not a PCN model "
            f"({', '.join(sorted(MODEL_ZOO))}) and the LM serving path "
            f"needs a missing module ({e.name})") from e


if __name__ == "__main__":
    main()
