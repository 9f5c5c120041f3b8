"""Shared arithmetic of the per-layer metric readers.

A reader's context ``ctx`` holds ``config`` and ``traffic`` (the cell's
files), ``trace`` (``bench.trace.reduce_planes`` of the traced window),
``peaks`` (``bench.peaks``), ``clouds`` (answers produced in the window)
and ``window_s``; the serving driver adds ``queue_wait_ms`` and
``padding_waste_pct``.  A reader returns None where it finds nothing to
read.
"""
from __future__ import annotations

from bench import families, flops


def idle_pct(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def kernel_roofline_pct(ctx, kernel: str):
    """Least time the chip needs for the kernel's calls in the window
    over their summed device time.  Each traced event is one call site
    for a whole batch; the configuration's family counts the call sites
    (``<kernel>_calls``)."""
    t = ctx["trace"]
    secs = t["kernel_s"].get(kernel, 0.0)
    n_events = t["kernel_calls"].get(kernel, 0)
    if secs <= 0 or n_events == 0:
        return None
    per_cloud = getattr(families.of(ctx["config"]),
                        kernel + "_calls")(ctx["config"])
    batch = ctx["traffic"]["batch"]
    steps = n_events / len(per_cloud)
    scaled = [{"flops": c["flops"] * batch, "bytes": c["bytes"] * batch}
              for c in per_cloud]
    return 100.0 * steps * flops.roofline_seconds(scaled, ctx["peaks"]) / secs


def step_runs(ctx):
    """Executions of the step's program inside the traced window (the
    compiled program that took most device time there), fractional at
    the window's edges; None where the trace shows none."""
    t = ctx["trace"]
    if not t or not t.get("module_s"):
        return None
    step = max(t["module_s"], key=t["module_s"].get)
    return t["module_runs"][step]
