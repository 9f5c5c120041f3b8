"""Plain reference of PointNet++ classification (set abstraction stack,
global MLP, head), in traditional or L-PCN semantics.

Per block: farthest point sampling of the centers, exact kNN, then the
shared point MLP on [p - c, f_p] and a max over the neighbours (in L-PCN
semantics with the hub cache of ``refcore``).  Then the global MLP on
[c - centroid, f_c] over the last centers, a max, and the head.

Also the family's operation and byte counts (``bench.flops``):

* ``model_flops_per_cloud``: the dense work of the published network,
  every center x every neighbour slot through the block MLP, plus the
  global MLP and the head.  The same in lpcn and traditional mode.
* ``gather_mlp_calls`` (dense FC kernel): S*K evaluations of the
  block-MLP layers that the kernel runs, its gathered operand, centers,
  slot mask, weights and result at float32.
* ``hub_reuse_calls`` (reuse FC kernel): the same layers on the island
  pools, H = S / subsets_per_island islands of C = cache_x * K cached
  points, its pool, slot and compensation operands, weights and result.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import refcore as rc
from bench.flops import F32, I32, mlp_flops, mlp_weight_bytes

CHUNK = 16          # clouds per device call (fixed, so shapes compile once)


@partial(jax.jit, static_argnames=("activation", "precision", "lpcn"))
def _block(xyz, feats, cidx, nbr, hub_xyz, cached, layers, *, activation,
           precision, lpcn):
    st = partial(rc.store, precision=precision)
    xyz, feats, hub_xyz = st(xyz), st(feats), st(hub_xyz)
    c = rc.gather(xyz, cidx)                               # (C, S, 3)
    p = rc.gather(xyz, nbr)                                # (C, S, K, 3)
    f = rc.gather(feats, nbr)                              # (C, S, K, F)
    y = rc.mlp(layers, jnp.concatenate([st(p - c[:, :, None]), f], -1),
               activation, precision)
    if lpcn:
        wc = rc.comp_matrix(layers, 3, precision)
        yh = rc.mlp(layers, jnp.concatenate([st(p - hub_xyz[:, :, None]), f],
                                            -1), activation, precision)
        yh = st(yh + rc.matmul(st(hub_xyz - c), wc, precision)[:, :, None, :])
        y = jnp.where(cached[..., None], yh, y)
    y = y.max(2)
    return jax.nn.relu(y) if activation == "block_end" else y


@partial(jax.jit, static_argnames=("activation", "precision"))
def _head(xyz, feats, glob, head, *, activation, precision):
    if glob:
        st = partial(rc.store, precision=precision)
        xyz = st(xyz)
        x = jnp.concatenate([st(xyz - st(xyz.mean(1, keepdims=True))),
                             feats], -1)
        feats = rc.mlp(glob, x, activation, precision)
    g = feats.max(1)
    return rc.mlp(head, g, "per_layer", precision)


def _hub_scores(keys: np.ndarray, sizes: tuple[int, ...]) -> list:
    """Per block, per cloud: the uniform score of every center from the
    cloud's key (split per block, then the islandization half), the draw
    by which random hubs are chosen."""
    def one(key):
        out = []
        for s in sizes:
            key, sub = jax.random.split(key)
            _, kisl = jax.random.split(sub)
            ks = jax.vmap(partial(jax.random.fold_in, kisl))(jnp.arange(s))
            out.append(jax.vmap(lambda k: jax.random.uniform(k, ()))(ks))
        return out
    got = jax.jit(jax.vmap(one))(jnp.asarray(keys, jnp.uint32))
    return [np.asarray(g) for g in got]


def _ways(cfg: dict, cur: np.ndarray, scores: list, lpcn: bool,
          bi: int = 0) -> list[list[dict]]:
    """The readings of blocks ``bi``.. of one cloud whose block input is
    ``cur``: each a list of per-block geometry (centers, neighbours and
    hub-cache plan), the plain reading first."""
    if bi == len(cfg["blocks"]):
        return [[]]
    b = cfg["blocks"][bi]
    out = []
    for cidx in rc.fps_ways(cur, b["n_centers"]):
        centers = cur[cidx]
        nbr = rc.knn(cur, centers, b["k"])
        st = {"xyz": cur, "cidx": cidx, "nbr": nbr}
        if lpcn:
            plans = []
            for flip in rc.tie_flips(rc.boundary_ties(centers,
                                                      cfg["island"])):
                members, solo, hub = rc.islands(centers, scores[bi],
                                                cfg["island"], flip)
                cached, hub_of = rc.reuse_plan(members, solo, hub, nbr,
                                               b["k"], cfg["island"])
                plans.append({**st, "cached": cached,
                              "hub_xyz": centers[hub_of]})
        else:
            plans = [{**st, "cached": np.zeros(nbr.shape, bool),
                      "hub_xyz": centers}]
        rest = _ways(cfg, centers, scores, lpcn, bi + 1)
        out += [[plan] + r for plan in plans for r in rest]
    return out


def structure(cfg: dict, clouds, keys, lpcn: bool) -> list[list[list]]:
    """Host geometry of every cloud: its readings (``_ways``), one per
    combination of the ways rounding may decide (``refcore``)."""
    sizes = tuple(b["n_centers"] for b in cfg["blocks"])
    scores = _hub_scores(keys, sizes) if lpcn else None
    return [_ways(cfg, np.asarray(pts, np.float32),
                  [s[ci] for s in scores] if lpcn else None, lpcn)
            for ci, pts in enumerate(clouds)]


def _stack(sts, key, pad_to=None):
    arrs = [s[key] for s in sts]
    if pad_to is not None:
        arrs = [np.concatenate([a, np.zeros((pad_to - a.shape[0],)
                                            + a.shape[1:], a.dtype)])
                for a in arrs]
    return jnp.asarray(np.stack(arrs))


def forward(cfg: dict, weights: dict, structs, precision: str,
            lpcn: bool) -> np.ndarray:
    """Logits (C, n_classes) of the clouds whose ``structure`` is given."""
    n = len(structs)
    logits = []
    for lo in range(0, n, CHUNK):
        chunk = structs[lo:lo + CHUNK]
        chunk = chunk + [chunk[-1]] * (CHUNK - len(chunk))
        feats = None
        for bi, b in enumerate(cfg["blocks"]):
            sts = [c[bi] for c in chunk]
            pad = cfg["points"] if bi == 0 else None
            xyz = _stack(sts, "xyz", pad)
            if feats is None:
                feats = xyz
            feats = _block(xyz, feats, _stack(sts, "cidx"), _stack(sts, "nbr"),
                           _stack(sts, "hub_xyz"), _stack(sts, "cached"),
                           weights[f"block{bi}"], activation=cfg["activation"],
                           precision=precision, lpcn=lpcn)
        last = jnp.asarray(np.stack([c[-1]["xyz"][c[-1]["cidx"]]
                                     for c in chunk]))
        out = _head(last, feats, weights.get("global", []), weights["head"],
                    activation=cfg["activation"], precision=precision)
        logits.append(np.asarray(out)[:min(CHUNK, n - lo)])
    return np.concatenate(logits)


def reference(cfg: dict, weights: dict, clouds, keys, mode: str,
              precision: str = "highest") -> np.ndarray:
    """Logits (C, A, n_classes) of each cloud (n_i, 3) with its raw PRNG
    key (2,): A readings of every cloud, the plain one first, then one
    per other way that rounding may decide (``structure``); a cloud with
    fewer repeats its first."""
    lpcn = mode == "lpcn"
    readings = structure(cfg, clouds, keys, lpcn)
    logits = forward(cfg, weights, [r for rs in readings for r in rs],
                     precision, lpcn)
    out = np.empty((len(readings), max(map(len, readings)),
                    logits.shape[-1]), logits.dtype)
    at = 0
    for ci, rs in enumerate(readings):
        out[ci] = logits[at]
        out[ci, :len(rs)] = logits[at:at + len(rs)]
        at += len(rs)
    return out


def block_dims(cfg: dict) -> list[list[int]]:
    """Per block: the MLP's full dims [3 + in, *mlp]."""
    out, f = [], cfg["in_feats"]
    for b in cfg["blocks"]:
        out.append([3 + f, *b["mlp"]])
        f = b["mlp"][-1]
    return out


def _kernel_dims(cfg: dict, dims: list[int]) -> list[int]:
    """The layers an FC kernel runs: the last two of a ``per_layer`` MLP
    (earlier layers run before the kernel), every layer of a
    ``block_end`` (all-linear) MLP."""
    if cfg["activation"] == "per_layer" and len(dims) > 3:
        return dims[-3:]
    return dims


def _global_and_head_dims(cfg: dict) -> tuple[list[int] | None, list[int]]:
    f = cfg["blocks"][-1]["mlp"][-1]
    g = None
    if cfg.get("global_mlp"):
        g = [3 + f, *cfg["global_mlp"]]
        f = cfg["global_mlp"][-1]
    return g, [f, *cfg["head"], cfg["n_classes"]]


def mlp_specs(cfg: dict) -> list[tuple[str, list[int], str]]:
    """(role, dims, activation) of every MLP, in network order: the
    blocks, the global MLP (if any), the head."""
    out = [(f"block{i}", dims, cfg["activation"])
           for i, dims in enumerate(block_dims(cfg))]
    g, h = _global_and_head_dims(cfg)
    if g is not None:
        out.append(("global", g, cfg["activation"]))
    out.append(("head", h, "per_layer"))
    return out


def model_flops_per_cloud(cfg: dict) -> int:
    total = 0
    for b, dims in zip(cfg["blocks"], block_dims(cfg)):
        total += b["n_centers"] * b["k"] * mlp_flops(dims)
    g, h = _global_and_head_dims(cfg)
    if g is not None:
        total += cfg["blocks"][-1]["n_centers"] * mlp_flops(g)
    return total + mlp_flops(h)


def gather_mlp_calls(cfg: dict) -> list[dict]:
    """One entry per gather_mlp call site, per cloud: flops, bytes."""
    calls = []
    for b, dims in zip(cfg["blocks"], block_dims(cfg)):
        kd = _kernel_dims(cfg, dims)
        s, k = b["n_centers"], b["k"]
        centers = 3 if kd[0] == dims[0] else 0   # else centred beforehand
        byts = (s * k * kd[0] * F32 + s * centers * F32 + s * k * I32
                + s * kd[-1] * F32 + mlp_weight_bytes(kd))
        calls.append({"flops": s * k * mlp_flops(kd), "bytes": byts})
    return calls


def hub_reuse_calls(cfg: dict) -> list[dict]:
    """One entry per hub_reuse call site, per cloud (lpcn mode only)."""
    isl = cfg["island"]
    calls = []
    for b, dims in zip(cfg["blocks"], block_dims(cfg)):
        kd = _kernel_dims(cfg, dims)
        s, k = b["n_centers"], b["k"]
        h = max(s // isl["subsets_per_island"], 1)
        c = int(isl["cache_x"] * k)
        m = isl["capacity"]
        byts = (h * c * kd[0] * F32 + h * m * k * I32
                + 2 * h * m * kd[-1] * F32 + mlp_weight_bytes(kd))
        calls.append({"flops": h * c * mlp_flops(kd), "bytes": byts})
    return calls
