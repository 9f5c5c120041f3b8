"""Kernel lint: static checks over ``pallas_call`` equations in a jaxpr.

Nothing here executes a kernel.  We walk a (closed) jaxpr, collect
every ``pallas_call`` equation — descending into ``jit`` / control-flow
sub-jaxprs — and check each call site's grid mapping:

* **K001** — the per-call VMEM block footprint (streamed operands
  double-buffered, resident operands single-buffered) must fit the
  declared budget.  This is the same byte model the tile planners in
  :mod:`repro.kernels.tiling` use, so plan and lint cannot drift.
* **K002** — every block's last dim must be a 128-lane multiple *or*
  the operand's full width (small side inputs like a 3-wide centre
  block legitimately stream their whole minor axis).
* **K003** — evaluating each operand's index map at the grid corners
  must never place a tile fully outside the operand (overhang of the
  final partial tile is fine; a whole out-of-bounds tile means the
  grid over-counts).
* **K004** — an operand whose index map is constant across the grid is
  VMEM-resident; its block must then cover the whole array, or part of
  the operand is silently unreachable.
* **K005** — ``dimension_semantics`` must match the grid rank, and any
  axis marked ``"parallel"`` must vary every *output* index map (two
  parallel grid steps writing one output block is a race).

The walker (:func:`pallas_call_sites`) is also the one implementation
of the dispatch-count invariant pinned by ``tests/test_batched_fc.py``
and the ``scripts/ci.sh`` batched-kernel smoke.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass

import numpy as np

from repro.kernels.tiling import LANE, block_bytes, call_footprint_bytes

from .findings import Finding

_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr", "branches")


def _subjaxprs(eqn):
    """Yield every sub-jaxpr of an equation (jit, scan, cond, ...)."""
    for key in _SUBJAXPR_PARAMS:
        v = eqn.params.get(key)
        if v is None:
            continue
        for item in v if isinstance(v, (tuple, list)) else (v,):
            jx = getattr(item, "jaxpr", item)
            if hasattr(jx, "eqns"):
                yield jx


@dataclass
class OperandInfo:
    """Static view of one pallas_call operand (input or output)."""
    index: int
    array_shape: tuple[int, ...]
    block_shape: tuple[int, ...]
    dtype: np.dtype
    is_output: bool
    resident: bool                      # index map constant over the grid
    tile_indices: tuple[tuple[int, ...], ...]  # index-map outputs at probed grid pts

    @property
    def block_elems(self) -> int:
        return int(np.prod([d for d in self.block_shape if isinstance(d, int)] or [1]))


@dataclass
class KernelSite:
    """One pallas_call equation, statically summarized."""
    name: str
    grid: tuple[int, ...]
    dimension_semantics: tuple | None
    operands: list[OperandInfo]
    where: str

    @property
    def footprint_bytes(self) -> int:
        streamed = sum(block_bytes(o.block_shape, o.dtype) for o in self.operands
                       if not o.resident)
        resident = sum(block_bytes(o.block_shape, o.dtype) for o in self.operands
                       if o.resident)
        return call_footprint_bytes(streamed, resident)


def _grid_probe_points(grid):
    """Corner points of the grid (plus origin) — cheap but covers the
    first/last tile of every axis, which is where OOB and residency
    violations show up for the affine index maps this repo uses."""
    if not grid:
        return [()]
    axes = [sorted({0, max(0, int(g) - 1)}) for g in grid]
    pts = list(itertools.product(*axes))
    return pts[:64]  # bound the work for absurd ranks


def _eval_index_map(bm, point):
    from jax import core as jcore
    closed = bm.index_map_jaxpr
    out = jcore.eval_jaxpr(closed.jaxpr, closed.consts,
                           *[np.int32(p) for p in point])
    return tuple(int(v) for v in out)


def _site_from_eqn(eqn, where: str) -> KernelSite:
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    comp = eqn.params.get("compiler_params") or {}
    sem = getattr(comp.get("mosaic_tpu"), "dimension_semantics", None)
    name = "pallas_call"
    nsi = eqn.params.get("name_and_src_info")
    if nsi is not None:
        name = getattr(nsi, "name", str(nsi))

    points = _grid_probe_points(grid)
    num_inputs = getattr(gm, "num_inputs", None)
    operands = []
    for i, bm in enumerate(gm.block_mappings):
        arr = bm.array_aval
        block = tuple(getattr(d, "block_size", 1) for d in bm.block_shape)
        try:
            tiles = tuple(_eval_index_map(bm, p) for p in points)
        except Exception:
            tiles = ()
        resident = bool(tiles) and len(set(tiles)) == 1
        operands.append(OperandInfo(
            index=i,
            array_shape=tuple(int(d) for d in arr.shape),
            block_shape=block,
            dtype=np.dtype(arr.dtype),
            is_output=(num_inputs is not None and i >= num_inputs),
            resident=resident,
            tile_indices=tiles,
        ))
    return KernelSite(name=name, grid=grid, dimension_semantics=sem,
                      operands=operands, where=where)


def pallas_call_sites(jaxpr, where: str = "jaxpr") -> list[KernelSite]:
    """Collect every pallas_call site in ``jaxpr`` (a ``Jaxpr`` or
    ``ClosedJaxpr``), descending into jit/scan/cond/while sub-jaxprs."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    sites: list[KernelSite] = []
    counters: dict[str, int] = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                nsi = eqn.params.get("name_and_src_info")
                base = getattr(nsi, "name", "pallas_call") if nsi else "pallas_call"
                k = counters.get(base, 0)
                counters[base] = k + 1
                sites.append(_site_from_eqn(eqn, f"{where}/{base}#{k}"))
                # kernel bodies can in principle nest pallas_calls; they
                # don't in this repo, so don't descend into eqn.params.
                continue
            for sub in _subjaxprs(eqn):
                walk(sub)

    walk(jx)
    return sites


def count_pallas_calls(jaxpr, grids: list | None = None) -> int:
    """Dispatch-count invariant: number of pallas_call sites.  If
    ``grids`` is given, each site's grid tuple is appended (the shape
    the migrated ``tests/test_batched_fc.py`` walker reported)."""
    sites = pallas_call_sites(jaxpr)
    if grids is not None:
        grids.extend(s.grid for s in sites)
    return len(sites)


def check_kernel_site(site: KernelSite, *, vmem_budget_mb: float) -> list[Finding]:
    out: list[Finding] = []
    budget = int(vmem_budget_mb * 2**20)
    fp = site.footprint_bytes
    if fp > budget:
        out.append(Finding(
            "K001",
            f"block footprint {fp / 2**20:.2f} MiB exceeds the "
            f"{vmem_budget_mb:.2f} MiB VMEM budget (grid={site.grid})",
            where=site.where))

    for o in site.operands:
        if not o.block_shape or not o.array_shape:
            continue
        last_blk, last_arr = o.block_shape[-1], o.array_shape[-1]
        role = "output" if o.is_output else f"operand {o.index}"
        if last_blk % LANE != 0 and last_blk != last_arr:
            out.append(Finding(
                "K002",
                f"{role}: block last dim {last_blk} is neither a multiple of "
                f"{LANE} nor the full array width {last_arr} "
                f"(block={o.block_shape}, array={o.array_shape})",
                where=site.where))

        # block_shape may omit leading mapped dims relative to the array
        # (vmapped calls); align the two shapes from the right.
        nd = min(len(o.block_shape), len(o.array_shape))
        blk = o.block_shape[-nd:]
        arr = o.array_shape[-nd:]
        for tile in o.tile_indices:
            if len(tile) != nd:
                break
            for d, (ti, bd, ad) in enumerate(zip(tile, blk, arr)):
                if ti < 0 or ti * bd >= ad:
                    out.append(Finding(
                        "K003",
                        f"{role}: index map emits tile index {ti} on dim {d} "
                        f"(block {bd}, array {ad}) — tile starts outside the "
                        f"operand",
                        where=site.where))
                    break
            else:
                continue
            break  # one K003 per operand is enough

        if o.resident and not o.is_output:
            if len(tilezip := list(zip(o.block_shape[-nd:], o.array_shape[-nd:]))):
                covered = all(bd >= ad for bd, ad in tilezip)
                at_origin = all(i == 0 for i in (o.tile_indices[0] if o.tile_indices else ()))
                if not (covered and at_origin):
                    out.append(Finding(
                        "K004",
                        f"operand {o.index} is resident (constant index map "
                        f"{o.tile_indices[0] if o.tile_indices else '?'}) but its block "
                        f"{o.block_shape} does not cover the array {o.array_shape}",
                        where=site.where))

    sem = site.dimension_semantics
    if sem is not None:
        if len(sem) != len(site.grid):
            out.append(Finding(
                "K005",
                f"dimension_semantics {tuple(sem)} has rank {len(sem)} but the "
                f"grid {site.grid} has rank {len(site.grid)}",
                where=site.where))
        else:
            for axis, s in enumerate(sem):
                if s != "parallel" or site.grid[axis] <= 1:
                    continue
                for o in site.operands:
                    if not o.is_output or len(o.tile_indices) < 2:
                        continue
                    # does this output's index map vary along `axis`?
                    pts = _grid_probe_points(site.grid)
                    by_rest = {}
                    varies = False
                    for p, t in zip(pts, o.tile_indices):
                        rest = tuple(v for a, v in enumerate(p) if a != axis)
                        if rest in by_rest and by_rest[rest] != t:
                            varies = True
                            break
                        by_rest.setdefault(rest, t)
                    if not varies:
                        out.append(Finding(
                            "K005",
                            f"grid axis {axis} is 'parallel' but output "
                            f"{o.index}'s index map does not vary along it "
                            f"(parallel iterations would race on one block)",
                            where=site.where))
    return out


def kernel_findings(jaxpr, *, vmem_budget_mb: float, where: str = "jaxpr") -> list[Finding]:
    """Run K001–K005 over every pallas_call site in ``jaxpr``."""
    out: list[Finding] = []
    for site in pallas_call_sites(jaxpr, where=where):
        out.extend(check_kernel_site(site, vmem_budget_mb=vmem_budget_mb))
    return out
