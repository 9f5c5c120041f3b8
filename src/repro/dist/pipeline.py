"""Pipeline parallelism: a GPipe-style microbatch schedule over one mesh
axis, built on ``shard_map`` + ``ppermute``.

Each device along the pipeline axis holds ONE stage's parameters; the
n_micro microbatches stream through the stages, one hop per step, for
``n_micro + n_stage - 1`` steps (the classic fill/drain bubble).  The
result equals applying the stages sequentially to every microbatch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(mesh, axis: str, n_micro: int, fn, stage_params, x):
    """Run ``x`` through a pipeline of stages laid out along ``axis``.

    fn(params, microbatch) -> microbatch   one stage's computation
    stage_params                            pytree, leaves (n_stage, ...)
    x                                       (n_micro, mb, ...) inputs

    Returns (n_micro, mb, ...) outputs, replicated across the mesh.
    Equivalent to ``for s in range(n_stage): x = fn(params[s], x)`` per
    microbatch — verified by tests/test_distributed.py.
    """
    n_stage = mesh.shape[axis]
    leading = {leaf.shape[0] for leaf in jax.tree.leaves(stage_params)}
    if leading != {n_stage}:
        raise ValueError(
            f"stage_params leading dims {sorted(leading)} != mesh axis "
            f"{axis!r} size {n_stage}")
    if x.shape[0] != n_micro:
        raise ValueError(f"x has {x.shape[0]} microbatches, expected "
                         f"{n_micro}")
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def stage_fn(params, xs):
        # params: this stage's (1, ...) slice; xs: all microbatches
        # (replicated — only stage 0 actually ingests them)
        p = jax.tree.map(lambda a: a[0], params)
        idx = jax.lax.axis_index(axis)
        recv0 = jnp.zeros(xs.shape[1:], xs.dtype)
        outs0 = jnp.zeros_like(xs)

        def step(carry, t):
            recv, outs = carry
            mb = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(idx == 0, mb, recv)
            y = fn(p, inp)
            # the last stage finishes microbatch t - (n_stage - 1)
            out_t = jnp.clip(t - (n_stage - 1), 0, n_micro - 1)
            take = (idx == n_stage - 1) & (t >= n_stage - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, out_t, 0,
                                               keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, y, cur), out_t, 0)
            recv = jax.lax.ppermute(y, axis, perm)
            return (recv, outs), None

        (_, outs), _ = jax.lax.scan(
            step, (recv0, outs0), jnp.arange(n_micro + n_stage - 1))
        # only the last stage's buffer holds real results; psum
        # replicates them (every other stage contributes zeros)
        return jax.lax.psum(
            jnp.where(idx == n_stage - 1, outs, jnp.zeros_like(outs)),
            axis)

    return jax.shard_map(stage_fn, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(), check_vma=False)(stage_params, x)
