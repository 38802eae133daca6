"""Pallas TPU decode attention over a latent (MLA) store.

One query token per slot, H heads, all reading the same latent rows
[S, D] (D = latent rank r + rope width): scores are q_h . row, and the
value of a row is its first r lanes, so K and V are one array and each
row is read once for every head. The store is read where it lies: a
layer stack [L, B, S, D] is indexed at the layer given as a
scalar-prefetch operand, so the decode step never slices or copies it.

Grid (B, S / bs). Each slot's valid length is prefetched too: blocks
wholly past it are neither fetched (the index map repeats the last valid
block, so no new DMA starts) nor computed, and rows past it within a
block are zeroed before use (an overhanging last block may hold
anything). Online softmax over the blocks in f32; the output is the
latent-space attention output [B, H, r] f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(nv_ref, ly_ref, q_ref, kv_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bs: int, r: int, scale: float, n_blocks: int):
    del ly_ref                       # consumed by the index map
    b, j = pl.program_id(0), pl.program_id(1)
    nv = nv_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j * bs < nv)
    def _block():
        q = q_ref[...]                                       # [H, D]
        row = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        kv = jnp.where(row < nv, kv_ref[...], 0).astype(q.dtype)  # [bs, D]
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        s = jnp.where(col < nv, s, NEG_INF)                  # [H, bs]
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :r], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _done():
        l = l_scr[...]
        o_ref[...] = acc_scr[...] / jnp.where(l > 0, l, 1.0)


@functools.partial(jax.jit, static_argnames=("kv_lora", "scale", "block_s",
                                             "interpret"))
def decode_mla_pallas(q, store, n_valid, layer, *, kv_lora: int,
                      scale: float, block_s: int = 1024,
                      interpret: bool = False):
    """q [B, H, D] in the compute dtype; store [B, S, D], or a layer stack
    [L, B, S, D] read at `layer` (int32 scalar; ignored otherwise);
    n_valid [B] int32. Returns [B, H, kv_lora] float32."""
    B, H, D = q.shape
    S = store.shape[-2]
    bs = S if S <= block_s else block_s
    n_blocks = pl.cdiv(S, bs)

    def row_block(b, j, nv_ref):
        # past the valid rows: repeat the last valid block (no new DMA)
        last = jnp.maximum(nv_ref[b] - 1, 0) // bs
        return jnp.minimum(j, last)

    if store.ndim == 4:
        kv_spec = pl.BlockSpec(
            (None, None, bs, D),
            lambda b, j, nv, ly: (ly[0], b, row_block(b, j, nv), 0))
    else:
        kv_spec = pl.BlockSpec(
            (None, bs, D), lambda b, j, nv, ly: (b, row_block(b, j, nv), 0))
    layer = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    call = pl.pallas_call(
        functools.partial(_kernel, bs=bs, r=kv_lora, scale=scale,
                          n_blocks=n_blocks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, n_blocks),
            in_specs=[pl.BlockSpec((None, H, D),
                                   lambda b, j, nv, ly: (b, 0, 0)),
                      kv_spec],
            out_specs=pl.BlockSpec((None, H, kv_lora),
                                   lambda b, j, nv, ly: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, kv_lora), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, kv_lora), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret)
    return call(n_valid.astype(jnp.int32), layer, q, store)
