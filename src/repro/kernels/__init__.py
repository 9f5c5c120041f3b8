"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Each kernel package holds ``<name>.py`` (the ``pallas_call``), ``ops.py``
(the jitted public wrapper) and ``ref.py`` (its jnp oracle).
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """The ``interpret`` flag a kernel wrapper runs with.

    An explicit bool wins.  ``None`` follows the default backend: the
    Pallas interpreter on ``"cpu"`` (tests), Mosaic on ``"tpu"``.  Any
    other platform raises instead of quietly running the interpreter."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas lowering for backend {backend!r}: the kernels compile "
        f"for 'tpu' and run interpreted on 'cpu' (JAX_PLATFORMS=cpu); "
        f"pass interpret=True explicitly to run the interpreter here")
