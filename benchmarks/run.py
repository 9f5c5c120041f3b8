"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = modeled
accelerator frame latency in µs where applicable, else wall-clock of the
measurement; derived = the figure's headline metric).

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only SECTION]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _emit(rows, name, us, derived, **meta):
    """meta (e.g. backend=..., batch=...) is recorded in the JSON output
    alongside the CSV fields."""
    rows.append((name, us, derived, meta))
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---- Fig. 4(b): overlap-vs-distance motivation study -----------------------

def bench_overlap_study(rows, quick: bool):
    import jax
    import jax.numpy as jnp
    from repro.core.pipeline import LPCNConfig, data_structuring
    from repro.core.workload import overlap_histogram
    from repro.data.synthetic import make_cloud
    rng = np.random.default_rng(0)
    xyz = jnp.asarray(make_cloud(rng, 1024))
    for sa, (s, k) in {"SA1": (512, 32), "SA2": (128, 64)}.items():
        cfg = LPCNConfig(n_centers=s, k=k)
        t0 = time.time()
        cidx, nbr = data_structuring(cfg, xyz, jax.random.PRNGKey(0))
        hist = overlap_histogram(nbr, xyz[cidx])
        us = (time.time() - t0) * 1e6
        near_mean, near_max = hist["near_0_16"]
        rest_mean, _ = hist["rest"]
        _emit(rows, f"fig4b_overlap_{sa}_top16", us,
              f"mean={near_mean:.3f} max={near_max:.3f} "
              f"rest_mean={rest_mean:.3f}")


# ---- Fig. 15: theoretical workload optimization -----------------------------

def bench_workload_reduction(rows, quick: bool):
    from .workloads import BENCHMARKS, layer_works, totals
    for name, (model, _ds, n) in BENCHMARKS.items():
        if quick and n > 4096:
            continue
        t0 = time.time()
        lw = layer_works(model, n)
        t = totals(lw)
        us = (time.time() - t0) * 1e6
        _emit(rows, f"fig15_workload_{name}", us,
              f"fetch_saving={t['fetch_saving']:.3f} "
              f"mem_saving={t['mem_saving']:.3f} "
              f"compute_saving={t['compute_saving']:.3f}")


# ---- Fig. 16: speedup over the four DS-accelerator baselines ---------------

def bench_speedup_baselines(rows, quick: bool):
    from .perfmodel import speedup
    from .workloads import BENCHMARKS, layer_works
    for name, (model, _ds, n) in BENCHMARKS.items():
        if quick and n > 4096:
            continue
        lw = layer_works(model, n)
        for method in ("pointacc", "hgpcn", "edgepc", "crescent"):
            s = speedup(method, lw)
            us = s["lpcn_ms"] * 1e3
            _emit(rows, f"fig16_{method}_{name}", us,
                  f"speedup={s['speedup']:.2f} "
                  f"dsu_frac={s['dsu_frac_baseline']:.2f} "
                  f"islu_frac={s['islu_frac']:.4f}")


# ---- Fig. 17: FC speedup vs GDPCA / Mesorasi --------------------------------

def bench_fc_speedup(rows, quick: bool):
    from .perfmodel import (fc_speedup_gdpca, fc_speedup_lpcn,
                            fc_speedup_mesorasi)
    from .workloads import BENCHMARKS, layer_works
    for name, (model, _ds, n) in BENCHMARKS.items():
        if quick and n > 4096:
            continue
        t0 = time.time()
        lw = layer_works(model, n)
        us = (time.time() - t0) * 1e6
        _emit(rows, f"fig17_fc_{name}", us,
              f"gdpca={fc_speedup_gdpca(lw):.2f} "
              f"lpcn={fc_speedup_lpcn(lw):.2f} "
              f"mesorasi_onchip={fc_speedup_mesorasi(lw, on_chip=True):.2f} "
              f"mesorasi_offchip="
              f"{fc_speedup_mesorasi(lw, on_chip=False):.2f}")


# ---- Fig. 18/19: large-scale PCNs (PointNeXt / PointVector) ----------------

def bench_large_scale(rows, quick: bool):
    from .perfmodel import fc_speedup_mesorasi, frame_latency
    from .workloads import LARGE_SCALE, layer_works, totals
    for name, (model, _ds, n) in LARGE_SCALE.items():
        if quick and n > 8192:
            continue
        t0 = time.time()
        # FractalCloud setting: block-based approximate DS (morton-strided
        # sampling + window gather) — also the only tractable DS at 65k+
        lw = layer_works(model, n, neighbor="edgepc", sampler="morton")
        t = totals(lw)
        # FractalCloud = block DS + Mesorasi delayed-aggregation FC;
        # L-PCN plug-in replaces the FC optimization
        base = frame_latency("crescent", lw, "traditional")
        ours = frame_latency("crescent", lw, "lpcn")
        mes_fc_speed = fc_speedup_mesorasi(lw, on_chip=False)
        fractal = base["dsu"] + base["fcu"] / max(mes_fc_speed, 1e-9)
        us = (time.time() - t0) * 1e6
        _emit(rows, f"fig18_19_{name}", us,
              f"fetch_saving={t['fetch_saving']:.3f} "
              f"compute_saving={t['compute_saving']:.3f} "
              f"speedup_vs_fractalcloud="
              f"{fractal / max(ours['total'], 1):.2f}")


# ---- Fig. 20: accuracy ------------------------------------------------------

def bench_accuracy(rows, quick: bool):
    from .accuracy import run_accuracy
    t0 = time.time()
    res = run_accuracy(quick=quick)
    us = (time.time() - t0) * 1e6
    for name, accs in res.items():
        _emit(rows, f"fig20_accuracy_{name}", us,
              " ".join(f"{k}={v:.3f}" for k, v in accs.items()))


# ---- Fig. 22: sensitivity ---------------------------------------------------

def bench_sensitivity(rows, quick: bool):
    from .perfmodel import speedup
    from .workloads import layer_works, totals
    sizes = [16, 32] if quick else [8, 16, 32, 64]
    caps = [2.0] if quick else [1.0, 2.0, 4.0]
    for isz in sizes:
        for cx in caps:
            t0 = time.time()
            lw = layer_works("pointnet2_c", 1024,
                             {"island_size": isz,
                              "island_capacity": 2 * isz,
                              "cache_capacity_x": cx})
            t = totals(lw)
            s = speedup("pointacc", lw)
            us = (time.time() - t0) * 1e6
            _emit(rows, f"fig22_sens_isz{isz}_cap{cx}", us,
                  f"fetch_saving={t['fetch_saving']:.3f} "
                  f"compute_saving={t['compute_saving']:.3f} "
                  f"speedup={s['speedup']:.2f}")


# ---- engine: batched serving path (repro.engine), per FC backend -----------

def bench_engine(rows, quick: bool):
    """Wall-clock of the jitted batch-first engine on pointnet2_c:
    compile once, then time steady-state batches per backend x mode, on a
    full batch AND a ragged (padded, n_valid-masked) batch — the delta is
    the masking overhead later perf PRs track."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from dataclasses import replace as _replace
    from repro import engine
    from repro.data.synthetic import make_cloud
    from repro.models import MODEL_ZOO

    _, spec = MODEL_ZOO["pointnet2_c"]
    batch, n = (2, 256) if quick else (4, 1024)
    if quick:
        from repro.models.common import BlockSpec
        spec = _replace(spec, blocks=(
            BlockSpec(64, 16, (32, 64)), BlockSpec(16, 16, (64, 128))))
    params = engine.init(jax.random.PRNGKey(0), spec)
    rng = np.random.default_rng(0)
    xyz = jnp.asarray(np.stack([make_cloud(rng, n) for _ in range(batch)]))
    # ragged config: clouds at 100% / ~75% / ~60% ... of n, cycled over
    # the batch (padding content = repeated rows; fully masked)
    ragged_sizes = [max(int(n * frac), 1) for frac, _ in
                    zip((1.0, 0.75, 0.6, 0.9) * batch, range(batch))]
    ragged_in = engine.Batch.make(
        xyz, key=jax.random.PRNGKey(2),
        n_valid=jnp.asarray(ragged_sizes, jnp.int32))
    batch_in = engine.Batch.make(xyz, key=jax.random.PRNGKey(1))
    configs = [("full", batch_in, [n] * batch),
               ("ragged", ragged_in, ragged_sizes)]
    for backend in ("reference", "pallas"):
        for mode in ("traditional", "lpcn"):
            f = jax.jit(partial(engine.apply, spec=spec, mode=mode,
                                fc_backend=backend))
            for tag, b_in, sizes in configs:
                f(params, b_in).block_until_ready()      # compile
                reps = 2 if quick else 5
                t0 = time.time()
                for _ in range(reps):
                    out = f(params, b_in)
                out.block_until_ready()
                us = (time.time() - t0) / reps * 1e6
                _emit(rows, f"engine_{spec.name}_{mode}_{backend}_{tag}",
                      us, f"clouds_per_s={batch / (us / 1e6):.1f}",
                      backend=backend, batch=batch, mode=mode, n_points=n,
                      ragged=(tag == "ragged"),
                      n_valid={"sizes": sizes,
                               "mean": float(np.mean(sizes)),
                               "min": int(min(sizes)),
                               "max": int(max(sizes))})


# ---- fc_kernel: vmap-of-kernels vs natively batched grid (A/B) --------------

def bench_fc_kernel(rows, quick: bool):
    """Three-way A/B of the two FC kernels on identical inputs: (a) the
    old path (jax.vmap of the single-cloud kernel), (b) the batched grid
    on the VMEM-budget *heuristic* plan, (c) the batched grid on the
    *autotuned* plan (``repro.launch.autotune`` winner, pulled from the
    plan store on the default resolution path).  Mechanism note: vmap's
    pallas batching rule also folds B into one pallas_call, but with the
    unplanned per-cloud body — hardcoded ts=8 / one island per step,
    unaligned lanes, no weight-resident index maps or dimension
    semantics; the ``per_cloud_dispatches`` field records the *logical*
    per-cloud program count of that schedule.

    Every batched row records the plan *actually resolved during its
    trace* (``plans.capture()``) — ``tile`` / ``tile_provenance`` are
    observed, not requested, and an autotuned row that silently fell
    back to the heuristic raises instead of mislabeling the
    measurement.  Winners tuned here persist to the plan store; the
    ``*_speedup_curve`` summary rows record autotuned-vs-vmap as a
    function of B.

    Timing: all variants of a cell are traced and warmed up front,
    then timed in alternating passes (min-of-reps per pass, min across
    passes), so slow drift in background host load cancels out of the
    reported ratios instead of penalizing whichever variant ran
    last."""
    import contextlib
    import jax
    import jax.numpy as jnp
    from repro.kernels import plans
    from repro.kernels.gather_mlp.ops import gather_mlp, gather_mlp_batched
    from repro.kernels.hub_reuse.ops import hub_reuse, hub_reuse_batched
    from repro.launch import autotune

    rng = np.random.default_rng(0)
    reps = 3 if quick else 7
    # parity cells (batched within a few % of vmap) need the min-of-N
    # estimate close to the true floor on both sides of the ratio, so
    # quick mode leans on extra alternating passes instead of long reps
    passes = 6 if quick else 3
    tune_reps = 5 if quick else 7
    tune_budget = 18 if quick else 40
    # always two batch sizes: the A/B's headline is how the gap scales
    # with B (the batched grid amortizes weights/tiling over all B clouds)
    batches = [2, 4] if quick else [2, 8]
    sk = (64, 8) if quick else (512, 32)

    plans.configure(plans.default_path())
    store = plans.active_store()

    def timed(f, *args):
        jax.block_until_ready(f(*args))                # compile + warmup
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            out = f(*args)
            jax.block_until_ready(out)
            best = min(best, time.time() - t0)
        return best * 1e6

    def _static_footprint(f, *args):
        """The kernel linter's static VMEM prediction for the traced
        call — recorded next to the measured time so bench results and
        static predictions can be cross-checked offline."""
        from repro.analysis import pallas_call_sites
        sites = pallas_call_sites(jax.make_jaxpr(f)(*args))
        return dict(static_vmem_bytes=[s.footprint_bytes for s in sites])

    def traced_variant(kernel, b, fn, args, expect):
        """Trace and warm a fresh jitted batched call, observing the
        tile plan its trace resolves; raise if the observed provenance
        is not the one this row claims (a silent fallback would
        mislabel the A/B).  Timing happens afterwards, interleaved
        with the other variants — the resolved plan is baked into the
        returned executable, so later store/bypass toggles can't
        change what it runs."""
        ctx = plans.bypass if expect == "heuristic" else contextlib.nullcontext
        # fresh closure per variant: jax's trace cache is keyed on
        # function identity, and a shared fn would let this trace reuse
        # the other variant's jaxpr — plan already baked in, capture
        # would see nothing
        f = jax.jit(lambda *a, _fn=fn: _fn(*a))
        with ctx(), plans.capture() as cap:
            jax.block_until_ready(f(*args))
            sf = _static_footprint(f, *args)
        used = [r["plan"] for r in cap
                if r["kernel"] == kernel and r["dims"].get("b") == b]
        if not used:
            raise RuntimeError(
                f"fc_kernel: no batched tile plan observed for {kernel} "
                f"b={b}")
        plan = used[-1]
        if plan["provenance"] != expect:
            raise RuntimeError(
                f"fc_kernel: batched {kernel} b={b} row ran a "
                f"{plan['provenance']!r} plan — expected {expect!r} "
                f"(silent fallback would mislabel the A/B)")
        return f, plan, sf

    def interleave(variants):
        """min-of-reps per variant, re-measured over alternating
        passes: each pass times every variant back to back, so slow
        drift in host load lands on all of them instead of on
        whichever variant happened to run last."""
        best = [float("inf")] * len(variants)
        for _ in range(passes):
            for i, (f, args) in enumerate(variants):
                best[i] = min(best[i], timed(f, *args))
        return best

    curve = {"gather_mlp": [], "hub_reuse": []}
    for b in batches:
        s, k = sk
        d, dc, hd, f = 35, 3, 64, 128
        raw = jnp.asarray(rng.normal(size=(b, s, k, d)), jnp.float32)
        ctr = jnp.asarray(rng.normal(size=(b, s, dc)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(d, hd)) * 0.1, jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(hd, f)) * 0.1, jnp.float32)
        b1 = jnp.zeros((hd,), jnp.float32)
        b2 = jnp.zeros((f,), jnp.float32)
        mask = jnp.asarray(rng.integers(0, 2, (b, s, k)), jnp.int32)
        gdims = {"b": b, "s": s, "k": k, "d": d, "dc": dc, "h": hd, "f": f}
        autotune.ensure_plan("gather_mlp", gdims, store=store,
                             budget=tune_budget, reps=tune_reps)
        gargs = (raw, ctr, mask)
        f_v = jax.jit(jax.vmap(
            lambda r, c, m: gather_mlp(r, c, w1, b1, w2, b2, mask=m)))
        gfn = (lambda r, c, m:
               gather_mlp_batched(r, c, w1, b1, w2, b2, mask=m))
        f_h, plan_h, sf_h = traced_variant(
            "gather_mlp", b, gfn, gargs, expect="heuristic")
        f_a, plan_a, sf_a = traced_variant(
            "gather_mlp", b, gfn, gargs, expect="autotuned")
        us_v, us_h, us_a = interleave(
            [(f_v, gargs), (f_h, gargs), (f_a, gargs)])
        shapes = {"s": s, "k": k, "d": d, "h": hd, "f": f}
        _emit(rows, f"fc_kernel_gather_mlp_vmap_b{b}", us_v,
              f"per_cloud_dispatches={b}", dispatch="vmap",
              per_cloud_dispatches=b, batch=b, shapes=shapes)
        _emit(rows, f"fc_kernel_gather_mlp_batched_b{b}", us_h,
              f"pallas_calls=1 speedup_vs_vmap={us_v / max(us_h, 1e-9):.2f}",
              dispatch="batched_grid", per_cloud_dispatches=1, batch=b,
              shapes=shapes, tile=plan_h, grid=[b, plan_h["grid_tiles"]],
              tile_provenance=plan_h["provenance"], **sf_h)
        _emit(rows, f"fc_kernel_gather_mlp_autotuned_b{b}", us_a,
              f"pallas_calls=1 speedup_vs_vmap={us_v / max(us_a, 1e-9):.2f} "
              f"speedup_vs_heuristic={us_h / max(us_a, 1e-9):.2f}",
              dispatch=("vmap_variant" if plan_a.get("variant") == "vmap"
                        else "batched_grid"),
              per_cloud_dispatches=(b if plan_a.get("variant") == "vmap"
                                    else 1), batch=b,
              shapes=shapes, tile=plan_a, grid=[b, plan_a["grid_tiles"]],
              tile_provenance=plan_a["provenance"], **sf_a)
        curve["gather_mlp"].append((b, us_v / max(us_a, 1e-9)))

        # quick mode shrinks the per-island dims but keeps the full
        # island count: the batched grid's edge over vmap is weight /
        # scheduling amortization ACROSS islands, and below ~16 islands
        # the cell degenerates to parity — not a workload the paper's
        # hub-sharing premise describes
        hn, c, m = (16, 32, 16) if quick else (16, 64, 32)
        pool = jnp.asarray(rng.normal(size=(b, hn, c, d)), jnp.float32)
        slot = jnp.asarray(rng.integers(-1, c, (b, hn, m, k)), jnp.int32)
        comp = jnp.asarray(rng.normal(size=(b, hn, m, f)) * 0.01,
                           jnp.float32)
        live = jnp.asarray(rng.integers(0, 2, (b, hn, m, k)), jnp.int32)
        hdims = {"b": b, "hn": hn, "c": c, "m": m, "k": k, "d": d,
                 "h": hd, "f": f}
        autotune.ensure_plan("hub_reuse", hdims, store=store,
                             budget=tune_budget, reps=tune_reps)
        hargs = (pool, slot, comp, live)
        f_v = jax.jit(jax.vmap(
            lambda p, sl, cp, lv: hub_reuse(p, sl, cp, w1, b1, w2, b2,
                                            live=lv)))
        hfn = (lambda p, sl, cp, lv:
               hub_reuse_batched(p, sl, cp, w1, b1, w2, b2, live=lv))
        f_h, plan_h, sf_h = traced_variant(
            "hub_reuse", b, hfn, hargs, expect="heuristic")
        f_a, plan_a, sf_a = traced_variant(
            "hub_reuse", b, hfn, hargs, expect="autotuned")
        us_v, us_h, us_a = interleave(
            [(f_v, hargs), (f_h, hargs), (f_a, hargs)])
        shapes = {"hn": hn, "c": c, "m": m, "k": k, "d": d, "h": hd, "f": f}
        _emit(rows, f"fc_kernel_hub_reuse_vmap_b{b}", us_v,
              f"per_cloud_dispatches={b}", dispatch="vmap",
              per_cloud_dispatches=b, batch=b, shapes=shapes)
        _emit(rows, f"fc_kernel_hub_reuse_batched_b{b}", us_h,
              f"pallas_calls=1 speedup_vs_vmap={us_v / max(us_h, 1e-9):.2f}",
              dispatch="batched_grid", per_cloud_dispatches=1, batch=b,
              shapes=shapes, tile=plan_h, grid=[b, plan_h["grid_tiles"]],
              tile_provenance=plan_h["provenance"], **sf_h)
        _emit(rows, f"fc_kernel_hub_reuse_autotuned_b{b}", us_a,
              f"pallas_calls=1 speedup_vs_vmap={us_v / max(us_a, 1e-9):.2f} "
              f"speedup_vs_heuristic={us_h / max(us_a, 1e-9):.2f}",
              dispatch=("vmap_variant" if plan_a.get("variant") == "vmap"
                        else "batched_grid"),
              per_cloud_dispatches=(b if plan_a.get("variant") == "vmap"
                                    else 1), batch=b,
              shapes=shapes, tile=plan_a, grid=[b, plan_a["grid_tiles"]],
              tile_provenance=plan_a["provenance"], **sf_a)
        curve["hub_reuse"].append((b, us_v / max(us_a, 1e-9)))

    for kern, pts in curve.items():
        _emit(rows, f"fc_kernel_{kern}_speedup_curve", 0.0,
              " ".join(f"b{bb}={sv:.2f}" for bb, sv in pts),
              curve=[{"batch": bb, "autotuned_speedup_vs_vmap": sv}
                     for bb, sv in pts])

    # ---- whole-model A/B: engine.apply, vmap vs heuristic vs autotuned -----
    from dataclasses import replace as _replace
    from functools import partial
    from repro import engine
    from repro.data.synthetic import make_cloud
    from repro.engine import BlockSpec
    from repro.models import MODEL_ZOO, dgcnn

    def engine_provenances(cap):
        return sorted({r["plan"]["provenance"] for r in cap
                       if r["dims"].get("b") is not None})

    # per-model point counts: the composite ratio only resolves the FC
    # dispatch effect when the FC stage is a non-trivial share of the
    # model — dgcnn's edge convolutions dominate at any n, but
    # pointnet2's structure stage swamps tiny FC cells, so its quick
    # config keeps n (and the block widths) large enough for the A/B
    # to measure the kernels rather than octree noise
    pn_n = 384 if quick else 512
    dg_n = 128 if quick else 512
    model_specs = {
        "pointnet2_c": (pn_n, _replace(MODEL_ZOO["pointnet2_c"][1], blocks=(
            BlockSpec(pn_n // 4, 16, (32, 64)),
            BlockSpec(pn_n // 8, 16, (64, 96))))),
        "dgcnn_c": (dg_n, _replace(dgcnn.with_points(dgcnn.DGCNN_C, dg_n),
                                   blocks=(
            BlockSpec(dg_n, 8, (24,), kind="edge", sampler="all"),
            BlockSpec(dg_n, 8, (32,), kind="edge", sampler="all")))),
    }
    for mname, (n, spec) in model_specs.items():
        params = engine.init(jax.random.PRNGKey(0), spec)
        for bsz in batches:
            xyz = jnp.asarray(np.stack(
                [make_cloud(rng, n) for _ in range(bsz)]))
            b_in = engine.Batch.make(xyz, key=jax.random.PRNGKey(1))
            autotune.autotune_model(spec, bsz, n, mode="lpcn", store=store,
                                    budget=tune_budget, reps=tune_reps)
            provs = {}
            g_v = jax.jit(partial(engine.apply, spec=spec, mode="lpcn",
                                  fc_backend="pallas_vmap"))
            jax.block_until_ready(g_v(params, b_in))
            provs["pallas_vmap"] = ["per_cloud"]
            g_h = jax.jit(partial(engine.apply, spec=spec, mode="lpcn",
                                  fc_backend="pallas"))
            with plans.bypass(), plans.capture() as cap:
                jax.block_until_ready(g_h(params, b_in))
            provs["pallas"] = engine_provenances(cap)
            g_a = jax.jit(partial(engine.apply, spec=spec, mode="lpcn",
                                  fc_backend="pallas"))
            with plans.capture() as cap:
                jax.block_until_ready(g_a(params, b_in))
            provs["pallas_autotuned"] = engine_provenances(cap)
            if provs["pallas_autotuned"] != ["autotuned"]:
                raise RuntimeError(
                    f"fc_kernel: engine {mname} b={bsz} autotuned row "
                    f"resolved {provs['pallas_autotuned']} plans — a "
                    f"silent fallback would mislabel the A/B")
            eargs = (params, b_in)
            t = interleave([(g_v, eargs), (g_h, eargs), (g_a, eargs)])
            times = dict(zip(
                ("pallas_vmap", "pallas", "pallas_autotuned"), t))
            us_v = times["pallas_vmap"]
            ratio_h = us_v / max(times["pallas"], 1e-9)
            ratio_a = us_v / max(times["pallas_autotuned"], 1e-9)
            for be, us in times.items():
                _emit(rows, f"fc_kernel_engine_{mname}_{be}_b{bsz}", us,
                      f"speedup_batched_vs_vmap={ratio_h:.2f} "
                      f"speedup_autotuned_vs_vmap={ratio_a:.2f}",
                      model=mname, batch=bsz, n_points=n, backend=be,
                      dispatch=("vmap" if be == "pallas_vmap"
                                else "batched_grid"),
                      tile_provenance=provs[be],
                      per_cloud_dispatches=(bsz if be == "pallas_vmap"
                                            else 1))
    store.save()


# ---- serve: continuous-batching trace replay --------------------------------

def bench_serve(rows, quick: bool):
    """Replays a synthetic ragged trace (Poisson arrivals, log-normal
    sizes) through the continuous-batching layer and records the
    user-facing serving metrics — e2e/queue-wait percentiles,
    throughput, padding waste, dispatch mix, overlap — as a sync-vs-
    async A/B at three offered loads: light (timeouts fire partial
    batches), heavy (batches fill; the headline comparison), and chaos
    (seeded FaultPlan pricing the degraded fallback path).  Each JSON
    row carries the full serve report; the ``serve_async_ab`` row is
    the headline: heavy-load p95 e2e latency and throughput, async vs
    sync, on the identical trace."""
    import jax
    from dataclasses import replace as _replace
    from repro import engine, serve
    from repro.data.synthetic import make_cloud
    from repro.models import MODEL_ZOO

    _, spec = MODEL_ZOO["pointnet2_c"]
    if quick:
        # 256-point clouds with launch-style reduced blocks (centers
        # capped at points//4), not the tiny 64-point spec the other
        # quick benches use: per-batch service must be big enough that
        # overlapping padding/readback with in-flight compute beats
        # the executor handoff cost, or the A/B reads as noise (on
        # tiny batches sync and async are a wash)
        spec = _replace(spec, blocks=tuple(
            _replace(b, n_centers=min(b.n_centers, 64),
                     k=min(b.k, 16)) for b in spec.blocks))
        sizes, n_med, n_req = [256, 384], 256, 16
    else:
        sizes, n_med, n_req = [512, 1024], 512, 64
    eng = engine.PCNEngine(spec, mode="lpcn", fc_backend="reference")
    params = eng.init(jax.random.PRNGKey(0))
    buckets = serve.BucketSet.make(sizes, batch=2 if quick else 4)
    reports: dict[tuple[str, str], dict] = {}
    for dmode, is_sync in (("sync", True), ("async", False)):
        server = serve.PCNServer(eng, params, buckets, timeout_s=0.01,
                                 max_in_flight=4, sync=is_sync)
        for load, rate in (("light", 30.0), ("heavy", 2000.0)):
            server.metrics = serve.ServeMetrics()  # fresh window per load
            events = serve.synthetic_trace(
                n_requests=n_req, rate_hz=rate, n_median=n_med,
                sigma=0.35, n_max=buckets.max_points, seed=1)
            rng = np.random.default_rng(0)
            rids = serve.replay(
                server, events,
                lambda n, i: (np.asarray(make_cloud(rng, n), np.float32),
                              None))
            rep = server.report(load=load, rate_hz=rate)
            assert all(server.ready(r) for r in rids), \
                "unanswered requests"
            reports[dmode, load] = rep
            lat = rep["latency_ms"]["e2e"]
            _emit(rows, f"serve_trace_{spec.name}_{load}_{dmode}",
                  1e3 * lat["mean"],
                  f"p50={lat['p50']:.1f} p95={lat['p95']:.1f} "
                  f"p99={lat['p99']:.1f} rps={rep['throughput_rps']:.1f} "
                  f"waste={rep['padding_waste_pct']:.1f}% "
                  f"overlap={rep['overlap']['overlap_pct']:.0f}%",
                  serve=rep)
        server.close()

        # chaos load: a seeded fault plan fails primary dispatches
        # mid-trace so the row prices the degraded (fallback-retried)
        # path — every request must still be answered, in both modes
        plan = serve.FaultPlan.bernoulli(
            seed=7, n_steps=n_req, p_fail=0.2, p_nan=0.1)
        server = serve.PCNServer(eng, params, buckets, timeout_s=0.01,
                                 faults=plan, max_in_flight=4,
                                 sync=is_sync)
        events = serve.synthetic_trace(
            n_requests=n_req, rate_hz=2000.0, n_median=n_med, sigma=0.35,
            n_max=buckets.max_points, seed=1)
        rng = np.random.default_rng(0)
        rids = serve.replay(
            server, events,
            lambda n, i: (np.asarray(make_cloud(rng, n), np.float32),
                          None))
        rep = server.report(load="chaos", rate_hz=2000.0)
        assert all(server.ready(r) and not server.failed(r)
                   for r in rids), \
            "chaos load: fallback must answer every request"
        server.close()
        reports[dmode, "chaos"] = rep
        lat = rep["latency_ms"]["e2e"]
        _emit(rows, f"serve_trace_{spec.name}_chaos_{dmode}",
              1e3 * lat["mean"],
              f"p50={lat['p50']:.1f} p99={lat['p99']:.1f} "
              f"degraded={rep['faults']['degraded_dispatches']} "
              f"injected={len(rep['fault_plan']['injected'])}",
              serve=rep)

    # headline A/B: same heavy trace, sync vs async dispatch
    hs, ha = reports["sync", "heavy"], reports["async", "heavy"]
    p95_s = hs["latency_ms"]["e2e"]["p95"]
    p95_a = ha["latency_ms"]["e2e"]["p95"]
    _emit(rows, f"serve_async_ab_{spec.name}_heavy", 1e3 * p95_a,
          f"p95_async={p95_a:.1f}ms p95_sync={p95_s:.1f}ms "
          f"rps_async={ha['throughput_rps']:.1f} "
          f"rps_sync={hs['throughput_rps']:.1f} "
          f"speedup={ha['throughput_rps'] / max(hs['throughput_rps'], 1e-9):.2f}x "
          f"overlap={ha['overlap']['overlap_pct']:.0f}% "
          f"depth<={ha['overlap']['inflight_depth_max']}",
          ab={f"{m}_{ld}": {"p95_e2e_ms": r["latency_ms"]["e2e"]["p95"],
                            "throughput_rps": r["throughput_rps"],
                            "overlap_pct": r["overlap"]["overlap_pct"]}
              for (m, ld), r in reports.items()})


# ---- dist: mesh-sharded engine vs single device -----------------------------

def _dist_records(quick: bool) -> list[dict]:
    """Sharded vs single-device ``engine.apply`` over every device this
    process sees, on identical inputs."""
    from dataclasses import replace
    from functools import partial

    import jax
    import jax.numpy as jnp

    from repro import engine
    from repro.data.synthetic import make_cloud
    from repro.engine import Batch, BlockSpec
    from repro.launch.mesh import make_mesh
    from repro.models import pointnet2

    n_dev = len(jax.devices())
    B, N = (n_dev, 128) if quick else (2 * n_dev, 512)
    spec = replace(pointnet2.POINTNET2_C, blocks=(
        BlockSpec(N // 4, 8, (16, 32)), BlockSpec(N // 8, 8, (32, 48))))
    params = engine.init(jax.random.PRNGKey(0), spec)
    rng = np.random.default_rng(0)
    xyz = jnp.asarray(np.stack([make_cloud(rng, N) for _ in range(B)]))
    batch = Batch.make(xyz, key=jax.random.PRNGKey(1))
    mesh = make_mesh((n_dev, 1), ("data", "model"))
    reps = 3 if quick else 8
    out = []
    for tag, mesh_arg in (("single_device", None), ("sharded", mesh)):
        f = jax.jit(partial(engine.apply, spec=spec, mode="lpcn",
                            mesh=mesh_arg))
        f(params, batch).block_until_ready()               # compile
        t0 = time.time()
        for _ in range(reps):
            y = f(params, batch)
        y.block_until_ready()
        us = (time.time() - t0) / reps * 1e6
        cps = B / (us / 1e6)
        devs = n_dev if mesh_arg is not None else 1
        out.append(dict(tag=tag, us=us, device_count=n_dev,
                        devices_used=devs,
                        platform=jax.devices()[0].platform,
                        mesh=None if mesh_arg is None else dict(mesh.shape),
                        batch=B, n_points=N, clouds_per_s=cps,
                        clouds_per_s_per_device=cps / devs))
    return out


def start_cpu_dist_worker(quick: bool):
    """CPU rehearsal of the dist section: a child process with forced
    host devices (the same trick as tests/test_distributed.py).  Only
    valid before this process imports JAX — a parent that holds a chip
    would starve the child — so ``main`` starts it first, and only
    under ``JAX_PLATFORMS=cpu``."""
    import subprocess
    import sys
    if "jax" in sys.modules:
        raise RuntimeError(
            "the forced-host-device dist worker must start before this "
            "process imports JAX; run `python -m benchmarks.run --only "
            "dist` with JAX_PLATFORMS=cpu")
    n_dev = 4 if quick else 8
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_dev} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.pathsep.join(
        [root, os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    code = ("import json; from benchmarks.run import _dist_records; "
            f"print('DIST_JSON ' + json.dumps(_dist_records({quick})))")
    return subprocess.Popen([sys.executable, "-c", code], env=env, cwd=root,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def bench_dist(rows, quick: bool, worker=None):
    """Mesh-sharded engine.apply (batch split over an (n, 1)
    ("data", "model") mesh) vs the single-device fast path on identical
    inputs.  On a chip host it runs in this process over
    ``jax.devices()`` (one process per chip); ``worker`` is the CPU
    rehearsal child from :func:`start_cpu_dist_worker`.  Records device
    count, mesh shape, and absolute + per-device throughput (on a CPU
    host the fake devices share the same cores, so sharded wall-clock is
    a schedule-overhead measurement, not a speedup claim)."""
    if worker is None:
        recs = _dist_records(quick)
    else:
        stdout, stderr = worker.communicate(timeout=1800)
        if worker.returncode != 0:
            raise RuntimeError(
                f"dist bench worker failed:\nSTDOUT:\n{stdout}\n"
                f"STDERR:\n{stderr}")
        line = [ln for ln in stdout.splitlines()
                if ln.startswith("DIST_JSON ")][-1]
        recs = json.loads(line[len("DIST_JSON "):])
    for rec in recs:
        tag, us = rec.pop("tag"), rec.pop("us")
        _emit(rows, f"dist_engine_{tag}_d{rec['device_count']}", us,
              f"clouds_per_s={rec['clouds_per_s']:.1f} "
              f"per_device={rec['clouds_per_s_per_device']:.1f} "
              f"mesh={rec['mesh']}", **rec)


SECTIONS = {
    "engine": bench_engine,
    "fc_kernel": bench_fc_kernel,
    "serve": bench_serve,
    "dist": bench_dist,
    "overlap": bench_overlap_study,
    "workload": bench_workload_reduction,
    "speedup": bench_speedup_baselines,
    "fc": bench_fc_speedup,
    "large": bench_large_scale,
    "accuracy": bench_accuracy,
    "sensitivity": bench_sensitivity,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="results/bench.json")
    args = ap.parse_args(argv)
    names = [n for n in SECTIONS if not args.only or n == args.only]
    worker = None
    if "dist" in names and os.environ.get("JAX_PLATFORMS") == "cpu":
        worker = start_cpu_dist_worker(args.quick)   # before JAX loads
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows: list = []
    print("name,us_per_call,derived")
    for name in names:
        if name == "dist":
            bench_dist(rows, args.quick, worker=worker)
        else:
            SECTIONS[name](rows, args.quick)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    json.dump([{"name": n, "us": u, "derived": d, **meta}
               for n, u, d, meta in rows],
              open(args.out, "w"), indent=1)


if __name__ == "__main__":
    main()
