"""Ragged-masking lint: reductions over point axes must be guarded.

The PR-2 bug class: a padded batch carries dead rows, and a
``reduce_max``/``reduce_sum`` over the point axis silently folds them
in.  The repo-wide contract is that every such reduction is *guarded*
— its operand passes through an ``n_valid``-style ``jnp.where`` (a
``select_n``) or a ±BIG/±inf sentinel fill immediately upstream.

This module runs a small dataflow over a traced jaxpr:

* a var becomes **guarded** when produced by ``select_n``, or when it
  is a sentinel constant (|value| ≥ 1e30 or infinite — the −BIG fill);
* guardedness propagates through elementwise/structural ops (any
  guarded operand guards the output);
* ``dot_general``/conv and the reductions themselves **consume** the
  guard — a matmul scrambles rows, so the mask must be re-applied
  before the next pool (exactly the repo idiom);
* **M001** fires on any float reduction (``reduce_max``,
  ``reduce_min``, ``reduce_sum``, ``argmax``, ``argmin``) over an axis
  whose size is in the target's *point-size set* with an unguarded
  operand.

Point sizes are the axis lengths where padding can live: the padded
cloud length N, every neighbor count K, and the center counts of
blocks whose sampler keeps all rows (downsampled center axes are fully
valid by construction — ``nv_levels`` goes ``None`` below a
downsampling block — so they are deliberately excluded).

The walk descends into ``jit``/``scan``/``while``/``cond``/custom-JVP
sub-jaxprs and into Pallas kernel bodies (mapping operand guardedness
through the kernel's refs), so the in-kernel ``-BIG`` masked pools are
analyzed too.  Nothing executes.
"""
from __future__ import annotations

import numpy as np

from .findings import Finding

SENTINEL_ABS = 1e30

#: checked reduction primitive -> True (all carry an ``axes`` param)
CHECKED = ("reduce_max", "reduce_min", "reduce_sum", "argmax", "argmin")

#: primitives that consume (kill) guardedness
KILL = ("dot_general", "conv_general_dilated") + CHECKED

_SUB_KEYS = ("jaxpr", "call_jaxpr")


def _is_sentinel_value(v) -> bool:
    try:
        arr = np.asarray(v)
    except Exception:
        return False
    if arr.size == 0 or not np.issubdtype(arr.dtype, np.floating):
        return False
    return bool(np.any(np.isinf(arr)) or np.max(np.abs(arr)) >= SENTINEL_ABS)


class _Walker:
    def __init__(self, point_sizes, where):
        self.point_sizes = frozenset(int(p) for p in point_sizes)
        self.where = where
        self.findings: dict[tuple, Finding] = {}

    def _guard_of(self, v, guard):
        if hasattr(v, "val"):          # Literal
            return _is_sentinel_value(v.val)
        return guard.get(v, False)

    def run_closed(self, closed, in_guards):
        jx = getattr(closed, "jaxpr", closed)
        guard = {}
        consts = getattr(closed, "consts", None) or []
        for cv, cval in zip(jx.constvars, consts):
            guard[cv] = _is_sentinel_value(cval)
        for v, g in zip(jx.invars, in_guards):
            guard[v] = bool(g)
        self._walk(jx, guard)
        return [self._guard_of(v, guard) for v in jx.outvars]

    def _sub_closed(self, eqn):
        for key in _SUB_KEYS:
            v = eqn.params.get(key)
            if v is not None:
                return v
        return None

    def _check_reduce(self, eqn, operand_guarded):
        operand = eqn.invars[0]
        aval = getattr(operand, "aval", None)
        if aval is None or not np.issubdtype(np.dtype(aval.dtype), np.floating):
            return
        axes = eqn.params.get("axes", ())
        shape = tuple(aval.shape)
        hits = [a for a in axes if a < len(shape) and shape[a] in self.point_sizes]
        if hits and not operand_guarded:
            name = eqn.primitive.name
            sizes = [shape[a] for a in hits]
            axes_s = ",".join(str(int(a)) for a in axes)
            shape_s = "x".join(map(str, shape))
            key = (name, shape, tuple(int(a) for a in axes))
            # location is bracket-free so fnmatch suppression patterns
            # don't collide with character-class syntax
            self.findings.setdefault(key, Finding(
                "M001",
                f"{name} over point axis(es) {[int(a) for a in hits]} "
                f"(size {sizes}) of "
                f"f{np.dtype(aval.dtype).itemsize * 8}({shape_s}) "
                f"with no n_valid mask / sentinel fill on the operand",
                where=f"{self.where}/{name}({shape_s})@axes({axes_s})"))

    def _walk(self, jx, guard):
        for eqn in jx.eqns:
            name = eqn.primitive.name
            ins = [self._guard_of(v, guard) for v in eqn.invars]
            any_in = any(ins)

            if name in CHECKED:
                self._check_reduce(eqn, ins[0])
                for v in eqn.outvars:
                    guard[v] = False
                continue
            if name == "select_n":
                for v in eqn.outvars:
                    guard[v] = True
                continue
            if name in KILL:
                for v in eqn.outvars:
                    guard[v] = False
                continue

            if name == "pallas_call":
                self._walk_pallas(eqn, ins, guard)
                continue
            if name == "scan":
                body = eqn.params["jaxpr"]
                nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
                g = list(ins)
                outs = self.run_closed(body, g)
                # one fixpoint-ish extra pass: feed carry guards back in
                g[nc:nc + ncar] = [a or b for a, b in
                                   zip(g[nc:nc + ncar], outs[:ncar])]
                outs = self.run_closed(body, g)
                for v, og in zip(eqn.outvars, outs):
                    guard[v] = og
                continue
            if name == "while":
                cn = eqn.params["cond_nconsts"]
                bn = eqn.params["body_nconsts"]
                carry = ins[cn + bn:]
                body_in = ins[cn:cn + bn] + carry
                outs = self.run_closed(eqn.params["body_jaxpr"], body_in)
                body_in = ins[cn:cn + bn] + [a or b for a, b in zip(carry, outs)]
                outs = self.run_closed(eqn.params["body_jaxpr"], body_in)
                self.run_closed(eqn.params["cond_jaxpr"], ins[:cn] + carry)
                for v, og in zip(eqn.outvars, outs):
                    guard[v] = og
                continue
            if name == "cond":
                branch_outs = [self.run_closed(br, ins[1:])
                               for br in eqn.params["branches"]]
                for i, v in enumerate(eqn.outvars):
                    guard[v] = any(bo[i] for bo in branch_outs if i < len(bo))
                continue

            sub = self._sub_closed(eqn)
            if sub is not None and hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                if name in ("jit", "closed_call", "core_call", "remat",
                            "checkpoint", "custom_jvp_call", "custom_vjp_call",
                            "custom_vjp_call_jaxpr"):
                    outs = self.run_closed(sub, ins)
                else:
                    # unknown higher-order primitive: analyze the body with
                    # all inputs guarded (no false positives inside) and
                    # pass guardedness through conservatively
                    outs = self.run_closed(sub, [True] * len(
                        getattr(sub, "jaxpr", sub).invars))
                    outs = [any_in for _ in eqn.outvars]
                for v, og in zip(eqn.outvars, outs):
                    guard[v] = bool(og)
                continue

            # default: elementwise/structural — any guarded operand
            # guards the output (reshape, broadcast, where-chains,
            # scatter canvases, concatenate, arithmetic, ...)
            for v in eqn.outvars:
                guard[v] = any_in

    def _walk_pallas(self, eqn, ins, guard):
        kj = eqn.params.get("jaxpr")
        kj = getattr(kj, "jaxpr", kj)
        if kj is None or not hasattr(kj, "eqns"):
            for v in eqn.outvars:
                guard[v] = any(ins)
            return
        kguard = {}
        # kernel invars: [index operands] + input refs + output refs
        # (+ scratch); eqn.invars covers the first two groups.
        for i, v in enumerate(kj.invars):
            kguard[v] = ins[i] if i < len(ins) else False
        # refs: `get` reads pass the ref's guardedness (default walk
        # handles it), `swap`/`masked_swap` writes update it
        self._walk_kernel(kj, kguard)
        for v in eqn.outvars:
            guard[v] = any(ins)

    def _walk_kernel(self, kj, kguard):
        for eqn in kj.eqns:
            name = eqn.primitive.name
            if name in ("swap", "masked_swap"):
                # write: ref absorbs the value's guardedness
                val_guard = any(self._guard_of(v, kguard)
                                for v in eqn.invars[1:])
                kguard[eqn.invars[0]] = val_guard
                for v in eqn.outvars:
                    kguard[v] = val_guard
                continue
            self._walk_single(eqn, kguard)

    def _walk_single(self, eqn, guard):
        tmp_jx = type("J", (), {"eqns": [eqn]})
        self._walk(tmp_jx, guard)


def masked_reduction_findings(closed_jaxpr, *, point_sizes,
                              where: str = "jaxpr") -> list[Finding]:
    """Run the M001 dataflow over ``closed_jaxpr``.

    ``point_sizes`` — axis lengths that hold potentially-padded point
    rows (cloud length N, neighbor counts K, all-sampler center counts).
    """
    w = _Walker(point_sizes, where)
    jx = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    w.run_closed(closed_jaxpr, [False] * len(jx.invars))
    return list(w.findings.values())
