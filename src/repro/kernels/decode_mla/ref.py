"""jnp oracle of `kernel.decode_mla_pallas`."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_mla_ref(q, store, n_valid, *, kv_lora: int, scale: float):
    """q [B, H, D] (absorbed queries: latent part, then rope part); store
    [B, S, D] latent rows; n_valid [B] valid rows. Every head scores
    every valid row, and the value of a row is its first `kv_lora` lanes.
    Returns [B, H, kv_lora] float32."""
    S = store.shape[1]
    s = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32),
                   store.astype(jnp.float32)) * scale
    valid = jnp.arange(S)[None, None] < n_valid[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
    p = jnp.where(valid, p, 0.0)
    return jnp.einsum("bhs,bsr->bhr", p,
                      store[..., :kv_lora].astype(jnp.float32))
