"""Weights of a configuration, made on the device from the seed.

Every MLP of the network (as its family's ``mlp_specs`` lists them) is a
list of (w, b) float32 pairs: He-normal matrices and N(0, 0.05) biases
(nonzero, so that the bias paths are checked too).  All of them come out
of one jitted call.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import families

BIAS_STD = 0.05


@partial(jax.jit, static_argnums=1)
def _make(key, shapes):
    out = []
    for dims in shapes:
        layers = []
        for a, b in zip(dims[:-1], dims[1:]):
            key, kw, kb = jax.random.split(key, 3)
            w = jax.random.normal(kw, (a, b), jnp.float32) * jnp.sqrt(2.0 / a)
            layers.append((w, BIAS_STD * jax.random.normal(kb, (b,),
                                                           jnp.float32)))
        out.append(layers)
    return out


def make(cfg: dict, key_words) -> dict[str, list]:
    """{role: [(w, b), ...]} on the default device, from raw uint32 key
    words (2,)."""
    specs = families.of(cfg).mlp_specs(cfg)
    shapes = tuple(tuple(d) for _, d, _ in specs)
    made = _make(jnp.asarray(key_words, jnp.uint32), shapes)
    return {role: layers for (role, _, _), layers in zip(specs, made)}
