"""Jitted public wrapper for flash attention."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import resolve_interpret
from .flash_attention import flash_attention_pallas
from .ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "tq", "tk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, tq: int = 128,
                    tk: int = 128, interpret: bool | None = None):
    return flash_attention_pallas(q, k, v, causal=causal, tq=tq, tk=tk,
                                  interpret=resolve_interpret(interpret))


__all__ = ["flash_attention", "attention_ref"]
