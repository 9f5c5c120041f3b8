"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

From the device planes (``/device:TPU:<n>``), line "XLA Ops": the union
of the operation intervals inside the traced window (busy time), the
summed time of the events of each named kernel, and the operations that
took most time; line "XLA Modules": how many executions of each compiled
program fell inside the window, counting one cut by the window's edge by
the share of it that lies inside.  From the host plane: the benchmark's own spans
(``bench.*``, written with ``jax.profiler.TraceAnnotation``), which bound
the window (``bench.window``) and name what the host was doing in each
gap in which the device was idle.

A TPU trace names each operation event by its whole HLO instruction;
the breakdown groups events by that name and shows it short: the
instruction's name, its opcode and its result shape without layouts.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
SHORT = 120


def short_name(name: str) -> str:
    """``%fusion.7 = f32[16,512]{1,0:T(8,128)} fusion(...), kind=...`` ->
    ``%fusion.7 fusion f32[16,512]``."""
    if " = " not in name:
        return name[:SHORT]
    lhs, rhs = name.split(" = ", 1)
    rhs = _LAYOUT.sub("", rhs)
    m = _OPCODE.search(rhs)
    if m is None:
        return f"{lhs} {rhs}"[:SHORT]
    return f"{lhs} {m.group(1)} {rhs[:m.start()].strip()}"[:SHORT]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def kernel_of(name: str, kernels) -> str | None:
    """The kernel an operation event is, or None.  A Pallas kernel is a
    custom call named after its kernel (``%gather_mlp.2 = ...
    custom-call(...)``); an operation that only takes a kernel's result as
    an operand (``fusion(%gather_mlp.2)``) is not that kernel."""
    base = name.partition(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
    return base if base in kernels else None


def reduce_planes(planes, kernels=("gather_mlp", "hub_reuse")) -> dict:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events`` carrying ``name``, ``start_ns`` and ``duration_ns``), as
    ``jax.profiler.ProfileData`` gives them.

    Returns seconds: ``window_s``, ``busy_s`` (averaged over the devices),
    per-kernel ``kernel_s`` and ``kernel_calls``, ``nonkernel_s``, per
    compiled program ``module_runs`` (executions inside the window,
    fractional at its edges, averaged over the devices) and
    ``module_s``, and the ``breakdown`` lists ``device_ops`` and
    ``idle_gaps``."""
    host_spans, devices, modules = [], [], []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(list(line.events))
                elif line.name == MODULES_LINE:
                    modules += list(line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError(f"the trace holds no device line {OPS_LINE!r}")
    lo, hi = windows[0]
    busy_ns, kernel_ns, kernel_calls = 0.0, defaultdict(float), \
        defaultdict(int)
    by_op = defaultdict(float)
    gaps = []
    kind_of: dict[str, str | None] = {}
    for events in devices:
        ivs = []
        for ev in events:
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if e <= s:
                continue
            ivs.append((s, e))
            if ev.name not in kind_of:
                kind_of[ev.name] = kernel_of(ev.name, kernels)
            k = kind_of[ev.name]
            if k is not None:
                kernel_ns[k] += e - s
                kernel_calls[k] += 1
            by_op[ev.name] += e - s
        merged = _union(ivs)
        busy_ns += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    module_runs, module_ns = defaultdict(float), defaultdict(float)
    for ev in modules:
        s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
        if e > s and ev.duration_ns > 0:
            module_runs[ev.name] += (e - s) / ev.duration_ns / n_dev
            module_ns[ev.name] += (e - s) / n_dev
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW_SPAN]

    def doing(s, e):
        """The benchmark span that covers most of (s, e); of two that
        cover as much, the shorter (inner) one."""
        best, key = "none", (0.0, 0.0)
        for n, hs, he in spans:
            cov = min(e, he) - max(s, hs)
            if cov > 0 and (cov, hs - he) > key:
                best, key = n, (cov, hs - he)
        return best.removeprefix(SPAN_PREFIX)

    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [[doing(s, e), (e - s) * 1e-9] for s, e in gaps[:TOP]]
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    total_kernel = sum(kernel_ns.values())
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns / n_dev * 1e-9,
        "kernel_s": {k: v / n_dev * 1e-9 for k, v in kernel_ns.items()},
        "kernel_calls": {k: v // n_dev for k, v in kernel_calls.items()},
        "nonkernel_s": (busy_ns - total_kernel) / n_dev * 1e-9,
        "module_runs": dict(module_runs),
        "module_s": {k: v * 1e-9 for k, v in module_ns.items()},
        "breakdown": {
            "device_ops": [[short_name(n), v / n_dev * 1e-9]
                           for n, v in top_ops],
            "idle_gaps": top_gaps,
        },
    }


def reduce_file(path: str, **kw) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, **kw)
