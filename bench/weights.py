"""Random weights from the seed, made on the device in one jitted call,
in the type they are served in.

The tree has the layout the program's model takes (`repro.nn.model`:
embedding table, final norm, and a `blocks.sub0` stack with one leading
layer axis), built here from the configuration's sizes alone so that the
benchmark's reference can use the same arrays without taking anything the
program made. Matrices are N(0, 1/fan_in); norm scales are 1 + N(0, 0.1^2)
so that a path which drops a norm scale is visible in the logits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def shapes(m: dict) -> dict:
    """{path: (shape, fan_in or 0 for a norm scale)} of the weight tree."""
    L, d, f = m["num_layers"], m["d_model"], m["d_ff"]
    hq = m["num_heads"] * m["head_dim"]
    hkv = m["num_kv_heads"] * m["head_dim"]
    out = {
        ("embed", "table"): ((m["vocab_size"], d), d),
        ("final_norm", "scale"): ((d,), 0),
        ("blocks", "sub0", "norm1", "scale"): ((L, d), 0),
        ("blocks", "sub0", "attn", "wq", "w"): ((L, d, hq), d),
        ("blocks", "sub0", "attn", "wk", "w"): ((L, d, hkv), d),
        ("blocks", "sub0", "attn", "wv", "w"): ((L, d, hkv), d),
        ("blocks", "sub0", "attn", "wo", "w"): ((L, hq, d), hq),
        ("blocks", "sub0", "norm2", "scale"): ((L, d), 0),
        ("blocks", "sub0", "mlp", "gate", "w"): ((L, d, f), d),
        ("blocks", "sub0", "mlp", "up", "w"): ((L, d, f), d),
        ("blocks", "sub0", "mlp", "down", "w"): ((L, f, d), f),
    }
    if not m["tie_embeddings"]:
        out[("head", "w")] = ((d, m["vocab_size"]), d)
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    return tree


def make(m: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The weight tree for sizes `m`, drawn from `seed` on the default
    device."""
    spec = shapes(m)

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


def abstract(m: dict, dtype=jnp.bfloat16, sharding=None) -> dict:
    """Shape-only twin of `make`, for compiling without a device."""
    return _nest({p: jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
                  for p, (s, _) in shapes(m).items()})
