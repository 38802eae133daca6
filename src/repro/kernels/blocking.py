"""Shared dispatch/tiling helpers for the Pallas kernels."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None = auto (compiled on TPU, interpret elsewhere); bool forces a
    mode — tests force True on CPU, a future non-TPU Pallas backend
    forces False instead of being silently mis-dispatched."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def tile(S: int, step: int, target: int) -> tuple[int, int]:
    """(block, padded length) for a grid axis the TPU compiler will tile.

    Mosaic takes a block whose trailing dims are multiples of the (8, 128)
    tiling or span the whole array. When S fits in max(target, step), one
    block spans it, so any S is legal. Otherwise S is covered by the fewest
    `step`-aligned blocks no wider than the target, spread evenly so the
    padding is under one `step` per block. The caller pads the axis to the
    returned length and masks the pad."""
    if S <= max(target, step):
        return S, S
    cap = max(step, target - target % step)
    n = -(-S // cap)
    bs = -(-S // n)
    bs += -bs % step
    return bs, n * bs


def pad_rows(x, n: int, fill=0):
    """x padded by n rows of `fill` along axis 1 (the sequence axis of
    every [B, S, ...] cache and attention operand)."""
    if not n:
        return x
    return jnp.pad(x, [(0, 0), (0, n)] + [(0, 0)] * (x.ndim - 2),
                   constant_values=fill)


def reads_in_place(kv_heads: int, head_dim: int) -> bool:
    """Whether a dense `[.., S, Hkv, D]` store can be read where it lies.

    The TPU lays such an array out row-major, each row's `(Hkv, D)` whole
    in its tiles, only when D fills whole 128-lane rows and Hkv whole
    sublane tiles (a multiple of 8, or 2 or 4, which have tiles of their
    own). Any other store XLA lays out with S further in, and a kernel
    that reads rows would have it relaid out in full at every call."""
    return head_dim % 128 == 0 and (kv_heads % 8 == 0 or kv_heads in (2, 4))
