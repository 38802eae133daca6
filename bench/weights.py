"""Random weights from the seed, made on the device in one jitted call,
in the type they are served in.

The tree is the one the configuration's architecture names
(`archs/<name>.py`, `weight_shapes`): the layout the program's model
takes, built from the configuration's sizes alone so that the
benchmark's reference can use the same arrays without taking anything the
program made. Matrices are N(0, 1/fan_in); norm scales are 1 + N(0, 0.1^2)
so that a path which drops a norm scale is visible in the logits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return tree


def make(c, seed: int, dtype=jnp.bfloat16) -> dict:
    """The weight tree of cell `c`'s configuration (a `harness.Cell`),
    drawn from `seed` on the default device."""
    spec = c.arch.weight_shapes(c.config)

    def draw(key):
        flat = {}
        for i, (path, (shape, fan_in)) in enumerate(sorted(spec.items())):
            k = jax.random.fold_in(key, i)
            if fan_in:
                x = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                    fan_in)
            else:
                x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            flat[path] = x.astype(dtype)
        return _nest(flat)

    key = jax.random.key(int(seed) % 2**32)
    return jax.jit(draw)(jax.random.fold_in(key, int(seed) >> 32))


def abstract(c, dtype=jnp.bfloat16, sharding=None) -> dict:
    """Shape-only twin of `make`, for compiling without a device."""
    return _nest({p: jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
                  for p, (s, _) in c.arch.weight_shapes(c.config).items()})
