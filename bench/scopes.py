"""The program's own stages and spans in a profiler trace.

The program runs each stage of a step under a flat ``jax.named_scope``
(``pcn.octree``, ``pcn.sample``, ``pcn.neighbors``, ``pcn.islandize``,
``pcn.schedule``, ``pcn.reuse_inputs``, ``pcn.overflow``,
``pcn.dense_inputs``, ``pcn.head``), and its server writes host spans
named ``serve.*`` with ``jax.profiler.TraceAnnotation``.

XLA carries each HLO instruction's JAX name stack into the trace as the
``tf_op`` stat of the device event's metadata, e.g.
``jit(pcn_step)/vmap(pcn.neighbors)/vmap(jit(knn_bruteforce))/top_k``.
``jax.profiler.ProfileData`` gives the events but not their metadata's
stats, so :func:`read_tf_ops` reads those from the serialized ``XSpace``
with a protobuf wire-format reader that skips each plane's lines (the
events, nearly all of the bytes).  A ``while`` carries no ``tf_op``; its
body's operations do.

:func:`reduce_stages` makes one pass over the traced window
(``bench.window``): each device instant belongs to the innermost
operation event that covers it, and that event's stage is the last
``pcn.<stage>`` of its ``tf_op``; an event without one (a ``while``, an
op outside every scope) takes the stage of the event around it, else
``unscoped``.  A kernel's custom call counts as kernel, as in
``bench.trace``.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench.trace import OPS_LINE, WINDOW_SPAN, _union, kernel_of

KERNELS = ("gather_mlp", "hub_reuse")
UNSCOPED = "unscoped"
SERVE_PREFIX = "serve."
#: spans of the server's own host work; device idle while one of them is
#: open is idle that the server causes
SERVE_HOST = ("serve.fire", "serve.pad", "serve.readback", "serve.complete")
_STAGE = re.compile(r"pcn\.([A-Za-z0-9_]+)")


# -- protobuf wire format (XSpace, XPlane, XEventMetadata, XStat) ----------

def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of one message in ``buf[lo:hi]``; a
    length-delimited value is its (start, end) in ``buf``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unknown protobuf wire type {wire} at {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """(key, value span) of a map entry."""
    key, value = 0, (span[0], span[0])
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_tf_ops(buf, lo, hi) -> dict:
    name, stat_names, events = "", {}, []
    for f, v in _fields(buf, lo, hi):
        if f == 2:
            name = _text(buf, v)
        elif f == 4:                               # event_metadata
            events.append(_map_value(buf, v)[1])
        elif f == 5:                               # stat_metadata
            key, span = _map_value(buf, v)
            for g, w in _fields(buf, *span):
                if g == 2:
                    stat_names[key] = _text(buf, w)
    if not name.startswith("/device:"):
        return {}
    out = {}
    for span in events:
        ev_name, stats = "", []
        for f, v in _fields(buf, *span):
            if f == 2:
                ev_name = _text(buf, v)
            elif f == 5:
                stats.append(v)
        for span_s in stats:
            meta, value = None, None
            for g, w in _fields(buf, *span_s):
                if g == 1:
                    meta = w
                elif g == 5:
                    value = _text(buf, w)
                elif g == 7:                       # a reference to a name
                    value = stat_names.get(w)
            if stat_names.get(meta) == "tf_op" and value:
                out[ev_name] = value
    return out


def read_tf_ops(raw: bytes) -> dict[str, str]:
    """{device operation event name: its ``tf_op``} of a serialized
    ``XSpace``, over every device plane."""
    buf = memoryview(raw)
    out = {}
    for f, v in _fields(buf, 0, len(buf)):
        if f == 1:
            out.update(_plane_tf_ops(buf, *v))
    return out


def stage_of(tf_op: str | None) -> str | None:
    """The last ``pcn.<stage>`` of a name stack, or None."""
    found = _STAGE.findall(tf_op or "")
    return found[-1] if found else None


# -- the pass over the traced window ---------------------------------------

def _overlap(a, b) -> float:
    """Summed overlap of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _device_pass(events, lo, hi, label_of, want_gaps):
    """Self time per label of one device's operation events inside
    [lo, hi], and (if ``want_gaps``) the idle gaps."""
    acc = defaultdict(float)
    gaps = []
    ivs = []
    for ev in events:
        s = max(ev.start_ns, lo)
        e = min(ev.start_ns + ev.duration_ns, hi)
        if e > s:
            ivs.append((s, -e, ev.name))
    ivs.sort()
    stack = []                 # open events, outermost first: (end, label)
    cur = lo

    def advance(t):
        """Give [cur, t) to the innermost open event, instant by instant
        as events close."""
        nonlocal cur
        while cur < t:
            while stack and stack[-1][0] <= cur:
                stack.pop()
            if not stack:
                if want_gaps:
                    gaps.append((cur, t))
                cur = t
                return
            end, label = stack[-1]
            nxt = min(end, t)
            acc[label] += nxt - cur
            cur = nxt

    for s, neg_e, name in ivs:
        advance(s)
        label = label_of(name)
        if label is None:
            while stack and stack[-1][0] <= s:
                stack.pop()
            label = stack[-1][1] if stack else UNSCOPED
        stack.append((-neg_e, label))
    advance(hi)
    return acc, gaps


def reduce_stages(planes, tf_ops: dict) -> dict:
    """``planes`` as ``jax.profiler.ProfileData`` gives them; ``tf_ops``
    from :func:`read_tf_ops` of the same trace.

    Returns seconds, averaged over the devices: ``window_s``, ``busy_s``,
    ``stage_s`` (each stage's own device time, ``unscoped`` included),
    ``kernel_s`` (per kernel); ``scoped`` (whether any operation of the
    trace carries a stage); ``serve_s`` (each ``serve.*`` span's time
    inside the window, summed over threads) and ``idle_in_serve_host_s``
    (device idle while a :data:`SERVE_HOST` span was open on some
    thread; None where the trace holds none)."""
    window, spans, devices = None, defaultdict(list), []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN and window is None:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith(SERVE_PREFIX):
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    if not devices:
        raise ValueError(f"the trace holds no device line {OPS_LINE!r}")
    lo, hi = window
    serve_s = {}
    host = []
    for name, ivs in spans.items():
        clipped = [(max(s, lo), min(e, hi)) for s, e in ivs]
        clipped = [(s, e) for s, e in clipped if e > s]
        serve_s[name] = sum(e - s for s, e in clipped) * 1e-9
        if name in SERVE_HOST:
            host += clipped
    host = _union(host)

    labels: dict[str, str | None] = {}

    def label_of(name):
        if name not in labels:
            k = kernel_of(name, KERNELS)
            labels[name] = ("kernel:" + k) if k else \
                stage_of(tf_ops.get(name))
        return labels[name]

    total, idle_in_host = defaultdict(float), 0.0
    for events in devices:
        acc, gaps = _device_pass(events, lo, hi, label_of, bool(host))
        for k, v in acc.items():
            total[k] += v
        idle_in_host += _overlap(gaps, host)
    n = len(devices)
    stage_s = {k: v / n * 1e-9 for k, v in total.items()
               if not k.startswith("kernel:")}
    kernel_s = {k.removeprefix("kernel:"): v / n * 1e-9
                for k, v in total.items() if k.startswith("kernel:")}
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(total.values()) / n * 1e-9,
        "stage_s": stage_s,
        "kernel_s": kernel_s,
        "scoped": any(stage_of(t) for t in tf_ops.values()),
        "serve_s": serve_s,
        "idle_in_serve_host_s": idle_in_host / n * 1e-9 if spans else None,
    }


def reduce_file(path: str) -> dict:
    """:func:`reduce_stages` of a ``.xplane.pb`` file (read once)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    return reduce_stages(ProfileData.from_serialized_xspace(raw).planes,
                         read_tf_ops(raw))
