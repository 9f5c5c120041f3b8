"""Plain reference of DGCNN classification (EdgeConv stack, embedding,
head), in traditional or L-PCN semantics.

Every point is a center, and one graph serves every layer: the exact k
nearest points of each point in coordinate space.  Per EdgeConv layer,
the linear edge map of [f_j - f_i, f_i] over the k neighbours j of each
point i, a max over them, then ReLU (``block_end``: the activation
follows the pool).  In L-PCN semantics a position read from the hub cache
(``refcore.islands`` and ``refcore.reuse_plan`` per layer) is the edge
map of [f_j - f_hub, f_hub] plus (f_hub - f_i) . Wc (``refcore.
comp_matrix(kind="edge")``), which is exact for a linear map.  Then the
concatenation of the four outputs, the per-point embedding, a max over
the points, its activation, and the head.

Where float32 rounding decides, the reference reads each way: a point
whose k-th and (k+1)-th nearest distances lie within rounding of each
other may keep either among its neighbours (``knn_ways``).  A center on
a voxel boundary of the islands needs no second reading here: with the
compensation exact, the island a center joins moves its answer only by
rounding.

Also the family's operation and byte counts (``bench.flops``):

* ``model_flops_per_cloud``: every point x every neighbour slot through
  each layer's edge map, every point through the embedding, and the head.
  The same in lpcn and traditional mode.
* ``gather_mlp_calls`` (dense FC kernel): the edge map of all N*K edges;
  the neighbours' and the centers' features, the slot mask, the weights
  and the result at float32.
* ``hub_reuse_calls`` (reuse FC kernel): the edge map of the island
  pools, H = N / subsets_per_island islands of C = cache_x * K cached
  points; the cached points' and the hubs' features, the slots, the
  compensation and the result.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import refcore as rc
from bench.families.pointnet2 import CHUNK, _hub_scores
from bench.flops import F32, I32, mlp_flops, mlp_weight_bytes


def knn_ways(pts: np.ndarray, k: int) -> list[np.ndarray]:
    """The kNN graphs (N, k) a device's rounding may give: the plain one
    (``refcore.knn``), then one per point whose k-th and (k+1)-th
    distances lie within ``refcore.FPS_TOL`` of each other, with the
    runner-up in place of the k-th; nearest ties first, at most
    ``refcore.MAX_TIES``."""
    d = rc.sq3(pts[:, None, :] - pts[None, :, :])
    order = np.argsort(d, axis=1, kind="stable")
    nbr = order[:, :k].astype(np.int32)
    rows = np.arange(pts.shape[0])
    kth, nxt = d[rows, order[:, k - 1]], d[rows, order[:, k]]
    gap = (nxt.astype(np.float64) - kth) / np.maximum(nxt, 1e-30)
    ways = [nbr]
    for i in np.argsort(gap, kind="stable")[:rc.MAX_TIES]:
        if gap[i] > rc.FPS_TOL:
            break
        alt = nbr.copy()
        alt[i, k - 1] = order[i, k]
        ways.append(alt)
    return ways


def structure(cfg: dict, clouds, keys, lpcn: bool) -> list[list[dict]]:
    """Host geometry of every cloud: one reading per kNN graph of
    ``knn_ways``, each the per-layer neighbours and hub-cache plan."""
    isl, n_layers = cfg["island"], len(cfg["blocks"])
    k = cfg["blocks"][0]["k"]
    if any(b["k"] != k or b["sampler"] != "all" for b in cfg["blocks"]):
        raise ValueError("the reference builds one graph of every point")
    scores = _hub_scores(keys, (cfg["points"],) * n_layers) if lpcn \
        else None
    out = []
    for ci, pts in enumerate(clouds):
        pts = np.asarray(pts, np.float32)
        isles = [rc.islands(pts, s[ci], isl) for s in scores] if lpcn \
            else []
        readings = []
        for nbr in knn_ways(pts, k):
            if lpcn:
                plans = [rc.reuse_plan(*m, nbr, k, isl) for m in isles]
            else:
                none = (np.zeros(nbr.shape, bool), np.arange(len(pts)))
                plans = [none] * n_layers
            readings.append({"xyz": pts, "nbr": nbr,
                             "cached": [c for c, _ in plans],
                             "hub_of": [h for _, h in plans]})
        out.append(readings)
    return out


@partial(jax.jit, static_argnames=("activation", "precision", "lpcn"))
def _edge_conv(feats, nbr, hub_of, cached, layers, *, activation,
               precision, lpcn):
    st = partial(rc.store, precision=precision)
    feats = st(feats)
    fi = feats[:, :, None, :]                              # (C, N, 1, F)
    fj = rc.gather(feats, nbr)                             # (C, N, K, F)
    y = rc.mlp(layers, jnp.concatenate(
        [st(fj - fi), jnp.broadcast_to(fi, fj.shape)], -1),
        activation, precision)
    if lpcn:
        d = feats.shape[-1]
        wc = rc.comp_matrix(layers, d, precision, kind="edge")
        fh = rc.gather(feats, hub_of)[:, :, None, :]       # (C, N, 1, F)
        yh = rc.mlp(layers, jnp.concatenate(
            [st(fj - fh), jnp.broadcast_to(fh, fj.shape)], -1),
            activation, precision)
        yh = st(yh + rc.matmul(st(fh - fi), wc, precision))
        y = jnp.where(cached[..., None], yh, y)
    y = y.max(2)
    return jax.nn.relu(y) if activation == "block_end" else y


@partial(jax.jit, static_argnames=("activation", "precision"))
def _embed_head(outs, glob, head, *, activation, precision):
    x = rc.mlp(glob, jnp.concatenate(outs, -1), activation, precision)
    g = x.max(1)                                           # (C, 1024)
    if activation == "block_end":
        g = jax.nn.relu(g)
    return rc.mlp(head, g, "per_layer", precision)


def forward(cfg: dict, weights: dict, readings: list[dict], precision: str,
            lpcn: bool) -> np.ndarray:
    """Logits (R, n_classes) of the readings given."""
    n, chunk = len(readings), min(CHUNK, len(readings))
    logits = []
    for lo in range(0, n, chunk):
        part = readings[lo:lo + chunk]
        part = part + [part[-1]] * (chunk - len(part))

        def stack(key, layer=None):
            return jnp.asarray(np.stack([r[key] if layer is None
                                         else r[key][layer] for r in part]))
        feats, nbr = stack("xyz"), stack("nbr")
        outs = []
        for li in range(len(cfg["blocks"])):
            feats = _edge_conv(feats, nbr, stack("hub_of", li),
                               stack("cached", li), weights[f"block{li}"],
                               activation=cfg["activation"],
                               precision=precision, lpcn=lpcn)
            outs.append(feats)
        out = _embed_head(outs, weights["global"], weights["head"],
                          activation=cfg["activation"], precision=precision)
        logits.append(np.asarray(out)[:min(chunk, n - lo)])
    return np.concatenate(logits)


def reference(cfg: dict, weights: dict, clouds, keys, mode: str,
              precision: str = "highest") -> np.ndarray:
    """Logits (C, A, n_classes) of each cloud (n_i, 3) with its raw PRNG
    key (2,): A readings of every cloud, the plain one first, then one
    per kNN tie (``knn_ways``); a cloud with fewer repeats its first."""
    readings = structure(cfg, clouds, keys, mode == "lpcn")
    logits = forward(cfg, weights, [r for rs in readings for r in rs],
                     precision, mode == "lpcn")
    out = np.empty((len(readings), max(map(len, readings)),
                    logits.shape[-1]), logits.dtype)
    at = 0
    for ci, rs in enumerate(readings):
        out[ci] = logits[at]
        out[ci, :len(rs)] = logits[at:at + len(rs)]
        at += len(rs)
    return out


def block_dims(cfg: dict) -> list[list[int]]:
    """Per EdgeConv layer: the edge map's dims [2 * in, *mlp]."""
    out, f = [], cfg["in_feats"]
    for b in cfg["blocks"]:
        out.append([2 * f, *b["mlp"]])
        f = b["mlp"][-1]
    return out


def _global_and_head_dims(cfg: dict) -> tuple[list[int], list[int]]:
    """The embedding's dims [concatenation, *global_mlp] and the head's."""
    g = [sum(b["mlp"][-1] for b in cfg["blocks"]), *cfg["global_mlp"]]
    return g, [g[-1], *cfg["head"], cfg["n_classes"]]


def mlp_specs(cfg: dict) -> list[tuple[str, list[int], str]]:
    """(role, dims, activation) of every MLP, in network order: the
    EdgeConv layers, the embedding, the head."""
    g, h = _global_and_head_dims(cfg)
    return [*((f"block{i}", dims, cfg["activation"])
              for i, dims in enumerate(block_dims(cfg))),
            ("global", g, cfg["activation"]), ("head", h, "per_layer")]


def model_flops_per_cloud(cfg: dict) -> int:
    total = sum(b["n_centers"] * b["k"] * mlp_flops(dims)
                for b, dims in zip(cfg["blocks"], block_dims(cfg)))
    g, h = _global_and_head_dims(cfg)
    return total + cfg["points"] * mlp_flops(g) + mlp_flops(h)


def gather_mlp_calls(cfg: dict) -> list[dict]:
    """One entry per gather_mlp call site, per cloud: flops, bytes."""
    calls = []
    for b, dims in zip(cfg["blocks"], block_dims(cfg)):
        s, k, f = b["n_centers"], b["k"], dims[0] // 2
        byts = (s * k * f * F32 + s * f * F32 + s * k * I32
                + s * dims[-1] * F32 + mlp_weight_bytes(dims))
        calls.append({"flops": s * k * mlp_flops(dims), "bytes": byts})
    return calls


def hub_reuse_calls(cfg: dict) -> list[dict]:
    """One entry per hub_reuse call site, per cloud (lpcn mode only)."""
    isl = cfg["island"]
    calls = []
    for b, dims in zip(cfg["blocks"], block_dims(cfg)):
        s, k, f = b["n_centers"], b["k"], dims[0] // 2
        h = max(s // isl["subsets_per_island"], 1)
        c = int(isl["cache_x"] * k)
        m = isl["capacity"]
        byts = (h * c * f * F32 + h * f * F32 + h * m * k * I32
                + 2 * h * m * dims[-1] * F32 + mlp_weight_bytes(dims))
        calls.append({"flops": h * c * mlp_flops(dims), "bytes": byts})
    return calls
