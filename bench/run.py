"""Run one cell of the benchmark once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse]

The cell, its configuration and its traffic are looked up by name:
``BENCHMARK.json`` names the configuration and the traffic of the cell,
``bench/configs/<config>.json`` holds the model and
``bench/traffic/<traffic>.json`` the traffic, whose ``driver`` names the
module under ``bench/drivers/`` that plays it.  ``bench/limits/<cell>.json``
holds the limits of the numbers that decide ``correct``, and each
per-layer metric is read by ``bench/metrics/<metric>.py``.

A run sets up (weights from the seed on the device, inputs, compile or
cache load, warm-up: ``setup_s``), measures for ``--seconds``, then
compares what the timed path produced with the plain reference
(``bench/families/<family>.py``, which also counts the family's
operations and bytes).  With ``--trace 1`` the window runs
under the profiler, for at most ``TRACE_SECONDS``, and the line carries the
per-layer metrics instead of the end-to-end ones.  The last line of standard output is one JSON
object; the numbers compared, each beside its limit, are also the last
lines of standard error.

Only a TPU is measured: on any other device the run exits non-zero with
no result.  ``--rehearse`` is the exception, for a CPU at a tiny size
(``JAX_PLATFORMS=cpu``): it shrinks the cell, runs the Pallas kernels in
interpret mode, stamps the line ``cpu`` and prints no metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
OUT = os.path.join(ROOT, ".bench_out")
#: longest traced window: reading a trace costs ~10x its length on the
#: host, and a run has to end within six minutes
TRACE_SECONDS = 10.0
CACHE = os.path.join(ROOT, ".jax_cache")


class Refused(SystemExit):
    """Exit without a result line."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's entries: workload, configuration, traffic, limits and
    the per-layer metrics that apply to it."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"bench: unknown workload {name!r}; known: "
                      f"{sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name])]
    return {"workload": w, "config": load_json(BENCH, "configs",
                                               w["config"] + ".json"),
            "traffic": load_json(BENCH, "traffic", w["traffic"] + ".json"),
            "limits": load_json(BENCH, "limits", name + ".json"),
            "end_to_end": e2e, "per_layer": layer}


def metric_reader(name: str):
    """``read(ctx) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver_class(traffic: dict):
    return importlib.import_module(
        f"bench.drivers.{traffic['driver']}").Driver


def require_devices(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devs[0].platform != want:
        raise Refused(f"bench: found {len(devs)} {devs[0].platform} "
                      f"device(s); this run needs {want} (a benchmark "
                      f"result is a TPU measurement; --rehearse is the "
                      f"CPU rehearsal)")
    if not rehearse and len(devs) < chips:
        raise Refused(f"bench: the cell needs {chips} chips, found "
                      f"{len(devs)}")
    return devs[:chips]


def set_libtpu_flags(cell: dict):
    """Add the traffic mix's TPU compiler flags to ``LIBTPU_INIT_ARGS``
    (before JAX starts), keeping what the variable already holds."""
    flags = " ".join(cell["traffic"].get("libtpu_flags", []))
    if flags:
        held = os.environ.get("LIBTPU_INIT_ARGS", "")
        os.environ["LIBTPU_INIT_ARGS"] = f"{held} {flags}".strip()


def configure_jax(cfg: dict, rehearse: bool):
    import jax
    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    elif not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    from repro.kernels import plans
    plans.configure(None)     # heuristic tile plans: no store, no tuner
    if rehearse:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")


class CompileCounter:
    """Counts backend compilations (none may happen in the window)."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def shrink(cell: dict) -> None:
    """The rehearsal's tiny size (in place): every point count and the
    sampled centers cut by the same factor, to 256-point clouds, batches
    of 2, a few requests a second."""
    cfg, tr = cell["config"], cell["traffic"]
    cut = cfg["points"] // 256
    cfg["points"] = 256
    for b in cfg["blocks"]:
        b["n_centers"] //= cut
    for key in ("points", "size_median", "size_min", "size_max"):
        if key in tr:
            tr[key] //= cut
    if "buckets" in tr:
        tr["buckets"] = [b // cut for b in tr["buckets"]]
    for key, small in (("batch", 2), ("bucket_batch", 2), ("pool_batches", 2),
                       ("check_requests", 4), ("warm_s", 1)):
        if key in tr:
            tr[key] = small
    if "rate_hz" in tr:
        tr["rate_hz"] = min(tr["rate_hz"], 4.0)


def device_info(devs, rehearse: bool) -> dict:
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearse:
        info["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs)
    return info


def checks(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct when every one is
    within its limit (a missing or NaN number is not)."""
    out, ok = {}, True
    for name, lim in limits.items():
        v = values.get(name)
        good = v is not None and v == v and v <= lim["limit"]
        ok &= good
        out[name] = {"value": v, "limit": lim["limit"]}
    return ok, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; no metrics")
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise Refused("bench: --seed must be >= 0")

    cell = load_cell(args.workload)
    if args.rehearse:
        shrink(cell)
    else:
        set_libtpu_flags(cell)
    from bench import program
    program.import_program()
    devs = require_devices(cell["workload"]["chips"], args.rehearse)
    configure_jax(cell["config"], args.rehearse)
    import jax
    import numpy as np

    from bench import families, peaks
    if not args.rehearse:
        peaks.peaks_for(devs[0].device_kind)      # unknown chip: no run
    compiles = CompileCounter()
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace \
        else args.seconds
    drv = driver_class(cell["traffic"])(cell, args.seed,
                                        families.of(cell["config"]), seconds)
    drv.setup()
    # Set-up leaves a heap of JAX and runtime objects that a full
    # collection would walk inside the window (a pause of the whole
    # process, the server's threads too); keep them out of later ones.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START

    trace_dir = os.path.join(OUT, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    n_compiles = compiles.n
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            drv.window(seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    in_window = compiles.n - n_compiles
    device = device_info(devs, args.rehearse)
    drv.release()

    clouds, keys = drv.check_inputs()
    ref = families.of(cell["config"]).reference(
        cell["config"], drv.weights, clouds, keys,
        cell["traffic"]["engine"]["mode"])
    values, attempted, answers = drv.check(ref)
    correct, compared = checks(values, cell["limits"])
    judged = answers[~np.isnan(answers)]
    failed = int((~(judged <= cell["limits"]["logit_gap"]["limit"])).sum())
    if in_window:
        correct = False
        compared["compiles_in_window"] = {"value": in_window, "limit": 0}

    metrics, breakdown = {}, None
    if args.rehearse:
        device["rehearsal"] = True
    elif args.trace:
        from bench import trace as tr
        summary = tr.reduce_file(tr.find_xplane(trace_dir))
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = summary["breakdown"]
        ctx = drv.layer_context(summary)
        ctx["peaks"] = peaks.peaks_for(devs[0].device_kind)
        for m in cell["per_layer"]:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        got = drv.end_to_end()
        got["setup_s"] = setup_s
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": got[m["name"]], "unit": m["unit"]}

    print("bench: " + json.dumps({"setup_s": setup_s, "compiles_in_window":
                                  in_window, **drv.diagnostics()}),
          file=sys.stderr)
    for name, c in compared.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = compared
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
