"""Shared arithmetic of the readers of the program's own scopes and
spans (``stage_ms.<stage>``, ``serve.idle_in_host_pct``).

They read the trace the run has just written: the newest ``.xplane.pb``
under ``.bench_out/trace/``, where ``bench.run`` puts the traced window,
reduced once by ``bench.scopes.reduce_file`` for every reader of the
run.  Where that reduction's window is not the one in ``ctx["trace"]``
(another run's trace), nothing is read.
"""
from __future__ import annotations

import functools
import os

from bench import scopes, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACES = os.path.join(ROOT, ".bench_out", "trace")


@functools.lru_cache(maxsize=1)
def _reduce(path: str, mtime: float) -> dict:
    return scopes.reduce_file(path)


def stages(ctx):
    """``bench.scopes.reduce_stages`` of the run's trace, or None."""
    t = ctx.get("trace")
    if not t:
        return None
    try:
        path = trace.find_xplane(TRACES)
    except FileNotFoundError:
        return None
    r = _reduce(path, os.path.getmtime(path))
    if abs(r["window_s"] - t["window_s"]) > 1e-9:
        return None
    return r


def stage_ms(ctx, stage: str):
    """The stage's own device time in ms per cloud answered in the
    window; None where the trace shows no time in it (``unscoped``:
    where no operation of the trace carries a stage)."""
    r = stages(ctx)
    if r is None or not ctx.get("clouds"):
        return None
    if stage == scopes.UNSCOPED:
        if not r["scoped"]:
            return None
        secs = r["stage_s"].get(stage, 0.0)
    else:
        secs = r["stage_s"].get(stage)
        if not secs:
            return None
    return 1e3 * secs / ctx["clouds"]


def idle_in_host_pct(ctx):
    """Share of the traced window in which the device was idle while the
    server's own host work (``scopes.SERVE_HOST``) had a span open."""
    r = stages(ctx)
    if r is None or r["idle_in_serve_host_s"] is None or r["window_s"] <= 0:
        return None
    return 100.0 * r["idle_in_serve_host_s"] / r["window_s"]
