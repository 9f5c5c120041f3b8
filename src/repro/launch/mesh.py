"""Production mesh builders (assignment-fixed shapes).

Functions, not module constants: importing this module never touches jax
device state.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / small-scale runs).  Every axis is
    ``Auto``: the engine and ``repro.dist`` place arrays with
    ``with_sharding_constraint``, which refuses explicit axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def local_mesh():
    """Whatever this host has (1 device on the dev container).

    Note the PCN engine does NOT need this on a single device: pass
    ``mesh=None`` (the default) to ``PCNEngine`` for the explicit
    no-mesh fast path — same numerics, no sharding machinery.
    """
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def data_mesh(n_data: int | None = None):
    """1-D data-parallel ("data", "model"=1) mesh for the PCN engine's
    sharded serving path.  Raises an actionable error when more shards
    are requested than this host has devices."""
    have = len(jax.devices())
    n = have if n_data is None else n_data
    if n < 1:
        raise ValueError(f"n_data must be >= 1, got {n}")
    if n > have:
        raise ValueError(
            f"requested a {n}-way data mesh but only {have} JAX "
            f"device(s) are visible; on CPU, force fake devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"(set BEFORE the first jax import) or lower the request "
            f"(e.g. serve --mesh-data {have})")
    return make_mesh((n, 1), ("data", "model"))
