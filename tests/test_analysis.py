"""repro.analysis: each rule family flags its golden known-bad fixture
with the right rule id, suppressions work, and the real engine matrix
passes clean (zero unsuppressed findings — the CI gate's contract)."""
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.analysis import (RULES, active, apply_suppressions,
                            compile_cache_size, count_pallas_calls,
                            kernel_findings, leaf_findings,
                            masked_reduction_findings, pallas_call_sites,
                            repo_findings, scan_suppressions,
                            static_findings)
from repro.analysis.cli import _src_suppressions, analyze_targets
from repro.analysis.retrace import cache_growth_findings
from repro.analysis.targets import default_targets

BIG = 3.4e38


def _rules(findings, unsuppressed_only=False):
    fs = active(findings) if unsuppressed_only else findings
    return sorted({f.rule for f in fs})


# ---- kernel lint golden fixtures -------------------------------------------

def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _trace_copy(shape, block, grid, index_map, *, out_block=None,
                out_index=None, semantics=None):
    """A minimal pallas_call with fully controllable specs (interpret
    mode — nothing executes, we only trace)."""
    params = {}
    if semantics is not None:
        from jax.experimental.pallas import tpu as pltpu
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=semantics)

    def fn(x):
        return pl.pallas_call(
            _copy_kernel, grid=grid,
            in_specs=[pl.BlockSpec(block, index_map)],
            out_specs=pl.BlockSpec(out_block or block,
                                   out_index or index_map),
            out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
            interpret=True, **params)(x)

    return jax.make_jaxpr(fn)(jnp.zeros(shape, jnp.float32))


def test_k001_over_budget_tile_plan():
    jx = _trace_copy((256, 256), (256, 256), (1,), lambda i: (0, 0))
    # 2 streamed-ish buffers of 256KB each easily bust a 0.1 MB budget
    fs = kernel_findings(jx, vmem_budget_mb=0.1, where="fixture")
    assert "K001" in _rules(fs), fs


def test_k002_misaligned_lane_kernel():
    # block last dim 64 is neither a 128-multiple nor the full width 256
    jx = _trace_copy((8, 256), (8, 64), (4,), lambda i: (0, i))
    fs = kernel_findings(jx, vmem_budget_mb=8.0, where="fixture")
    assert "K002" in _rules(fs), fs


def test_k002_full_width_small_operand_is_clean():
    # a 3-wide block streaming the full 3-wide axis (the ctr pattern)
    jx = _trace_copy((16, 3), (8, 3), (2,), lambda i: (i, 0))
    fs = kernel_findings(jx, vmem_budget_mb=8.0, where="fixture")
    assert "K002" not in _rules(fs), fs


def test_k003_out_of_bounds_grid_tile():
    # 16 rows / block 8 = 2 tiles, but the grid claims 4
    jx = _trace_copy((16, 128), (8, 128), (4,), lambda i: (i, 0))
    fs = kernel_findings(jx, vmem_budget_mb=8.0, where="fixture")
    assert "K003" in _rules(fs), fs


def test_k004_resident_operand_not_covering():
    # constant index map (resident) but the block covers half the rows
    jx = _trace_copy((256, 128), (128, 128), (2,), lambda i: (0, 0),
                     out_block=(128, 128), out_index=lambda i: (i, 0))
    fs = kernel_findings(jx, vmem_budget_mb=8.0, where="fixture")
    assert "K004" in _rules(fs), fs


def test_k005_parallel_axis_write_race():
    # grid axis 0 marked "parallel" but the output block never moves
    jx = _trace_copy((8, 128), (8, 128), (2,), lambda i: (0, 0),
                     semantics=("parallel",))
    fs = kernel_findings(jx, vmem_budget_mb=8.0, where="fixture")
    assert "K005" in _rules(fs), fs


def test_real_batched_kernel_is_clean_and_counted():
    """The PR-3 batched gather-MLP kernel passes every K rule at the
    default budget, and the migrated dispatch-count walker sees exactly
    one pallas_call with the batch in the grid."""
    from repro.kernels.gather_mlp.gather_mlp import gather_mlp_batched_pallas
    b, s, k, d, dc = 3, 16, 8, 6, 3
    args = (jnp.zeros((b, s, k, d)), jnp.zeros((b, s, dc)),
            jnp.zeros((d, 16)), jnp.zeros((16,)),
            jnp.zeros((16, 8)), jnp.zeros((8,)))
    jx = jax.make_jaxpr(
        lambda *a: gather_mlp_batched_pallas(*a, interpret=True))(*args)
    assert kernel_findings(jx, vmem_budget_mb=8.0) == []
    grids = []
    assert count_pallas_calls(jx, grids) == 1
    assert grids[0][0] == b, grids
    (site,) = pallas_call_sites(jx)
    assert site.footprint_bytes > 0
    # the weights ride constant index maps -> resident
    assert sum(o.resident for o in site.operands) >= 4, site.operands


# ---- masking lint golden fixtures ------------------------------------------

def test_m001_unmasked_reduction_flagged():
    jx = jax.make_jaxpr(lambda y: jnp.max(y, axis=1))(
        jnp.zeros((4, 8, 16)))
    fs = masked_reduction_findings(jx, point_sizes={8}, where="fixture")
    assert _rules(fs) == ["M001"], fs


def test_m001_sentinel_masked_reduction_clean():
    def fn(y, mask):
        return jnp.max(jnp.where(mask[..., None], y, -BIG), axis=1)
    jx = jax.make_jaxpr(fn)(jnp.zeros((4, 8, 16)),
                            jnp.zeros((4, 8), bool))
    assert masked_reduction_findings(jx, point_sizes={8}) == []


def test_m001_zero_fill_sum_clean():
    def fn(y, mask):
        return jnp.where(mask[..., None], y, 0.0).sum(axis=1)
    jx = jax.make_jaxpr(fn)(jnp.zeros((4, 8, 16)),
                            jnp.zeros((4, 8), bool))
    assert masked_reduction_findings(jx, point_sizes={8}) == []


def test_m001_guard_consumed_by_matmul():
    """A mask applied BEFORE a matmul does not guard a pool after it —
    the mask must be re-applied at the reduction."""
    def fn(y, mask, w):
        h = jnp.where(mask[..., None], y, 0.0) @ w    # (4, 8, 16)
        return jnp.max(h, axis=1)                      # unguarded again
    jx = jax.make_jaxpr(fn)(jnp.zeros((4, 8, 16)),
                            jnp.zeros((4, 8), bool),
                            jnp.zeros((16, 16)))
    fs = masked_reduction_findings(jx, point_sizes={8})
    assert _rules(fs) == ["M001"], fs


def test_m001_non_point_axis_ignored():
    jx = jax.make_jaxpr(lambda y: jnp.max(y, axis=2))(
        jnp.zeros((4, 8, 16)))
    assert masked_reduction_findings(jx, point_sizes={8}) == []


# ---- recompile-hazard golden fixtures --------------------------------------

def test_r001_numpy_leaf_into_jit():
    fs = leaf_findings({"x": np.zeros((3,), np.float32),
                        "y": jnp.zeros((3,))}, where="fx")
    assert _rules(fs) == ["R001"], fs
    assert "x" in fs[0].where


def test_r002_python_scalar_leaf():
    fs = leaf_findings({"s": 2.0, "y": jnp.zeros((3,))})
    assert _rules(fs) == ["R002"]
    assert fs[0].severity == "warning"


def test_r003_unhashable_static():
    fs = static_findings({"spec": [1, 2, 3], "mode": "lpcn"})
    assert _rules(fs) == ["R003"], fs


def test_r004_cache_growth_across_leaf_types():
    f = jax.jit(lambda x: x * 2)
    a = np.ones((4,), np.float32)
    fs = cache_growth_findings(f, [(a,), (jnp.asarray(a),)], expected=1)
    assert _rules(fs) == ["R004"], fs
    g = jax.jit(lambda x: x * 2)
    assert cache_growth_findings(
        g, [(jnp.ones((4,)),), (jnp.zeros((4,)),)], expected=1) == []
    assert compile_cache_size(g) == 1


# ---- repo lint golden fixtures ---------------------------------------------

def _write(root, rel, text):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(textwrap.dedent(text))


@pytest.fixture
def bad_repo(tmp_path):
    src = str(tmp_path / "src")
    _write(src, "repro/__init__.py", "")
    _write(src, "repro/dist/__init__.py", "")
    _write(src, "repro/engine/__init__.py", """\
        import repro.dist
        """)
    _write(src, "repro/core/bad.py", """\
        import time

        import jax


        def sample(key, n):
            t0 = time.time()
            idx = jax.random.choice(key, n, shape=(4,))
            return idx, t0


        def sample_ok(key, n):
            # analysis: allow A001 -- golden-fixture suppression test
            idx = jax.random.choice(key, n, shape=(4,))
            return idx


        def sample_unjustified(key, n):
            idx = jax.random.choice(key, n, shape=(4,))  # analysis: allow A001
            return idx
        """)
    _write(src, "repro/serve/__init__.py", "")
    _write(src, "repro/serve/bad.py", """\
        def fire_swallowing(fn, batch):          # A004: silently eaten
            try:
                return fn(batch)
            except Exception:
                pass


        def fire_bare(fn, batch):                # A004: bare except
            try:
                return fn(batch)
            except:
                return None


        def fire_converting(fn, batch, outcomes):  # ok: uses the error
            try:
                return fn(batch)
            except Exception as e:
                outcomes.append(repr(e))


        def fire_reraising(fn, batch):           # ok: re-raises
            try:
                return fn(batch)
            except Exception:
                raise RuntimeError("dispatch failed")


        def fire_narrow(fn, batch):              # ok: not a blanket catch
            try:
                return fn(batch)
            except KeyError:
                return None


        def fire_and_forget(pool, fn, batch):    # A005: result discarded
            pool.submit(fn, batch)
            return True


        def fire_state_check_only(pool, fn, batch):  # A005: .done() never
            fut = pool.submit(fn, batch)             # surfaces the error
            return fut.done()


        def fire_joined(pool, fn, batch):        # ok: joined inline
            return pool.submit(fn, batch).result()


        def fire_callback(rec, pool, fn, batch):  # ok: completion path
            rec.future = pool.submit(fn, batch)
            rec.future.add_done_callback(print)


        def fire_handed_off(pool, fn, batch, futs):  # ok: escapes to the
            f = pool.submit(fn, batch)               # caller, who owns it
            futs.append(f)


        def admit(queue, xyz):                   # ok: not a future at all
            req = queue.submit(xyz)
            return req.rid
        """)
    # the same swallow OUTSIDE repro.serve is not A004's business, and
    # the same dropped submit outside it is not A005's
    _write(src, "repro/launch/swallow.py", """\
        def best_effort(fn):
            try:
                return fn()
            except Exception:
                return None


        def best_effort_submit(pool, fn):
            pool.submit(fn)
        """)
    return src


def test_forbidden_ast_patterns_flagged(bad_repo):
    fs = repo_findings(bad_repo)
    rules = _rules(fs, unsuppressed_only=True)
    assert "A001" in rules and "A002" in rules and "A003" in rules, fs
    # A004: exactly the two swallowing handlers in repro.serve — the
    # converting / re-raising / narrow ones and the swallow outside the
    # serving layer stay clean
    a004 = [f for f in active(fs) if f.rule == "A004"]
    assert len(a004) == 2, a004
    assert all("serve/bad.py" in f.where for f in a004)
    assert any("bare except" in f.message for f in a004)
    assert any("except Exception" in f.message for f in a004)
    # A005: exactly the discarded submit and the state-check-only future
    # — joined / callback'd / escaping bindings, the non-future
    # queue.submit, and the drop outside repro.serve all stay clean
    a005 = [f for f in active(fs) if f.rule == "A005"]
    assert len(a005) == 2, a005
    assert all("serve/bad.py" in f.where for f in a005)
    assert any("result discarded" in f.message for f in a005)
    assert any("never consumed" in f.message for f in a005)
    # the justified suppression took effect...
    suppressed = [f for f in fs if f.suppressed]
    assert [f.rule for f in suppressed] == ["A001"]
    assert "golden-fixture" in suppressed[0].justification
    # ...the justification-less one did not, and was itself reported
    assert "S001" in rules, fs
    unsup_a001 = [f for f in active(fs) if f.rule == "A001"]
    assert len(unsup_a001) == 2  # the plain one + the unjustified one


def test_suppression_scan_syntax(tmp_path):
    p = str(tmp_path / "x.py")
    with open(p, "w") as fh:
        fh.write("# analysis: allow K002 */fc* -- lane-padded by hand\n"
                 "# analysis: allow M001\n")
    sups, meta = scan_suppressions(p)
    assert len(sups) == 1 and sups[0].rule == "K002"
    assert sups[0].pattern == "*/fc*"
    assert len(meta) == 1 and meta[0].rule == "S001"


# ---- the clean-repo pass (what `--strict` gates in CI) ---------------------

def test_repo_source_is_clean():
    fs = repo_findings()
    assert active(fs) == [], [str(f) for f in active(fs)]


def test_engine_matrix_clean_no_false_positives():
    """A representative slice of the matrix (the masked lpcn path on
    the batched pallas backend + the reference oracle, plus dgcnn whose
    sampler='all' keeps masks live at every level) yields zero
    unsuppressed findings — the zero-false-positive contract."""
    targets = [t for t in default_targets(
        models=("pointnet2", "dgcnn"), modes=("lpcn",),
        backends=("reference", "pallas"),
        include_serve=True, include_dist=False)]
    sups, _meta = _src_suppressions(None)
    findings, inventory = analyze_targets(targets, suppressions=sups)
    assert active(findings) == [], [str(f) for f in active(findings)]
    # the pallas targets contribute kernel sites to the inventory
    assert any(row["grid"][0] == 3 for row in inventory), inventory
    assert all(row["footprint_bytes"] > 0 for row in inventory)


def test_cli_quick_strict_and_report(tmp_path):
    from repro.analysis.cli import main
    out = str(tmp_path / "report.json")
    rc = main(["--quick", "--strict", "--json", out])
    assert rc == 0
    rep = json.load(open(out))
    assert rep["summary"]["strict_ok"] is True
    assert rep["summary"]["errors"] == 0
    assert rep["kernel_sites"], "quick matrix should include pallas targets"
    assert set(rep["rules"]) == set(RULES)
