"""Rotary position embeddings (applied at K-insert time, so evicted caches
keep pre-rotated keys — the standard serving layout the surveyed eviction
methods assume)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

Array = jax.Array


def rope_freqs(head_dim: int, theta: float) -> Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim/2]


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor m(mscale)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(head_dim: int, theta: float, yarn) -> Array:
    """YaRN inverse frequencies (`configs.base.YarnScaling`): the original
    frequencies above the correction range, those divided by the factor
    below it, and a linear ramp between, over pair index i."""
    extra = rope_freqs(head_dim, theta)
    inter = extra / yarn.factor

    def corr(rotations):          # pair index that turns `rotations` times
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def apply_rope(x: Array, positions: Array, theta: float, *,
               yarn=None, interleaved: bool = False) -> Array:
    """x: [..., T, H, D]; positions: broadcastable to [..., T].

    yarn: optional `YarnScaling` — YaRN frequencies, and cos/sin scaled by
    m(mscale) / m(mscale_all_dim). interleaved: the rotated pairs are
    (x[2i], x[2i+1]) — they are gathered to the half-split layout first,
    so the result is in that layout (DeepSeek-V2's convention)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta) if yarn is None else yarn_freqs(d, theta,
                                                                yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., T, D/2]
    angles = angles[..., None, :]                      # [..., T, 1, D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        s = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        cos, sin = cos * s, sin * s
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    )
    return out.astype(x.dtype)
