"""The seam to the system under test: a configuration file becomes the
program's model spec, and the benchmark's weights its parameter tree.

This is the only module of the benchmark that imports the program
(``repro``, from ``src/`` beside the benchmark), apart from the drivers
that call its engine and server.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def import_program():
    """Put ``src/`` on the path; fails (ImportError) where the checkout
    holds only the benchmark."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401


def spec(cfg: dict):
    """The program's ``PCNSpec`` of a configuration.  The spec's name is
    the configuration's, whose leading token selects the program's
    architecture family."""
    from repro.engine.spec import BlockSpec, PCNSpec
    blocks = tuple(
        BlockSpec(b["n_centers"], b["k"], tuple(b["mlp"]),
                  radius=b["radius"], kind=b["kind"], sampler=b["sampler"],
                  neighbor=b["neighbor"])
        for b in cfg["blocks"])
    if cfg["name"].split("_")[0] != cfg["family"]:
        raise ValueError(f"configuration {cfg['name']!r} must start with "
                         f"its family {cfg['family']!r}")
    return PCNSpec(name=cfg["name"], blocks=blocks,
                   head_dims=tuple(cfg["head"]), n_classes=cfg["n_classes"],
                   in_feats=cfg["in_feats"], task=cfg["task"],
                   global_mlp=tuple(cfg.get("global_mlp") or ()),
                   activation=cfg["activation"])


def isl_kw(cfg: dict) -> dict:
    """The configuration's island settings as the engine's ``isl_kw``."""
    i = cfg["island"]
    return {"island_size": i["subsets_per_island"],
            "island_capacity": i["capacity"],
            "cache_capacity_x": i["cache_x"],
            "octree_level": i["octree_level"],
            "overflow_frac": i["overflow_frac"],
            "hub_select": i["hub_select"],
            "compensation": i["compensation"]}


def params(cfg: dict, weights: dict):
    """The program's ``PCNParams`` holding the benchmark's weights."""
    from repro.core.mlp import MLP, Dense
    from repro.engine.params import PCNParams

    def as_mlp(role, activation):
        return MLP([Dense(w=w, b=b) for w, b in weights[role]], activation)

    n_blocks = len(cfg["blocks"])
    return PCNParams(
        blocks=tuple(as_mlp(f"block{i}", cfg["activation"])
                     for i in range(n_blocks)),
        head=as_mlp("head", "per_layer"),
        global_mlp=(as_mlp("global", cfg["activation"])
                    if "global" in weights else None))
