"""Blocked causal (optionally sliding-window) flash attention for
train/prefill — the O(T²) memory problem that makes 32k-prefill feasible.

Grid: (B, Hq, n_q, n_k); the kv-block axis is innermost/sequential so the
online-softmax accumulators persist in VMEM scratch. GQA maps the q-head
grid axis onto kv heads inside the BlockSpec index maps (h // group).
Fully-masked kv blocks (beyond causal diagonal / behind the window) are
skipped with pl.when — on TPU their loads are still prefetched by the
pipeline but no FLOPs are burned; the §Perf pass measures whether a
tighter index-map (diagonal-banded grid) is worth it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, window: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    needed = k_start <= q_start + bq - 1          # causal reachability
    if window > 0:
        needed = jnp.logical_and(needed, k_start + bk - 1 > q_start - window)

    @pl.when(needed)
    def _work():
        q = q_ref[0, 0].astype(jnp.float32)        # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)        # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = (q @ k.T) * scale                      # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos <= qpos
        if window > 0:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        out_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                         ).astype(out_ref.dtype)


def _chunk_kernel(off_ref, q_ref, k_ref, v_ref, out_ref, m_scr, l_scr,
                  acc_scr, *, bq: int, bk: int, window: int, scale: float):
    """Rectangular variant for chunked prefill: Tq (one prompt segment)
    attends over Tk (the full prompt scratch) at absolute query offset
    `off_ref[0]` — scalar-prefetched so the offset stays a traced operand
    (one compile per segment *length*, not per offset). The causal mask
    compares absolute positions, so scratch rows beyond the segment end
    (still zero) are masked exactly like the monolithic kernel masks
    future rows."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    n_k = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = off_ref[0] + iq * bq
    k_start = ik * bk
    needed = k_start <= q_start + bq - 1          # causal reachability
    if window > 0:
        needed = jnp.logical_and(needed, k_start + bk - 1 > q_start - window)

    @pl.when(needed)
    def _work():
        q = q_ref[0, 0].astype(jnp.float32)        # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)        # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = (q @ k.T) * scale                      # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos <= qpos
        if window > 0:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        out_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                         ).astype(out_ref.dtype)


def _verify_kernel(q_ref, k_ref, v_ref, kvp_ref, bias_ref, qp_ref, out_ref,
                   m_scr, l_scr, acc_scr, *, bq: int, bk: int, window: int,
                   scale: float):
    """Speculative-verify variant of the chunk kernel: the query block is
    one speculated segment (last committed token + drafts, already
    appended to the cache), the key axis is the *materialized cache view*
    [main store | residual ring] — rows live at arbitrary absolute
    positions (`kvp_ref`) with a validity bias (`bias_ref`), unlike the
    prefill kernels' implicit arange. Causality is therefore a gather of
    explicit positions: key row s is visible to query row t iff
    ``kv_pos[s] <= q_pos[t]`` (and within the sliding window), which
    masks both empty slots (bias) and the segment's own future drafts
    (position test) — the same mask `nn.attention.verify_attention`
    builds, run as one online-softmax pass per query block."""
    ik = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)            # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)
    qp = qp_ref[0]                                 # [bq, 1] int32
    kvp = kvp_ref[0, 0]                            # [1, bk] int32
    bias = bias_ref[0, 0]                          # [1, bk] f32
    s = (q @ k.T) * scale + bias                   # [bq, bk]
    ok = kvp <= qp
    if window > 0:
        ok = jnp.logical_and(ok, kvp > qp - window)
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + p @ v
    m_scr[...] = m_new

    @pl.when(ik == n_k - 1)
    def _done():
        out_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                         ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def flash_verify_pallas(q, k, v, kv_pos, bias, q_pos, *, window: int = 0,
                        bk: int = 512, interpret: bool = False):
    """q: [B, L, Hq, D] (one speculated segment, L small — a single query
    block); k, v: [B, Tk, Hkv, D] materialized cache view; kv_pos: [B, Tk]
    int32 absolute positions (-1 = empty); bias: [B, Tk] f32 additive
    validity; q_pos: [B, L] int32 (pad rows use a large negative position
    so every key is masked). Returns out [B, L, Hq, D]."""
    B, L, Hq, D = q.shape
    Tk = k.shape[1]
    Hkv = k.shape[2]
    Gq = Hq // Hkv
    bk = min(bk, Tk)
    assert Tk % bk == 0, (Tk, bk)
    n_k = Tk // bk
    qh = q.transpose(0, 2, 1, 3)                   # [B, Hq, L, D]
    kh = k.transpose(0, 2, 1, 3)                   # [B, Hkv, Tk, D]
    vh = v.transpose(0, 2, 1, 3)
    # per-key rows ride on a unit axis and query positions are a column,
    # so every block's last two dims span the array's (TPU tiling rule)
    out = pl.pallas_call(
        functools.partial(_verify_kernel, bq=L, bk=bk, window=window,
                          scale=1.0 / math.sqrt(D)),
        grid=(B, Hq, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, L, D), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h // Gq, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j: (b, h // Gq, j, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, bk), lambda b, h, j: (b, j, 0, 0)),
            pl.BlockSpec((1, L, 1), lambda b, h, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, L, D), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, L, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((L, 1), jnp.float32),
            pltpu.VMEM((L, 1), jnp.float32),
            pltpu.VMEM((L, D), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh, kv_pos.astype(jnp.int32).reshape(B, n_k, 1, bk),
      bias.astype(jnp.float32).reshape(B, n_k, 1, bk),
      q_pos.astype(jnp.int32).reshape(B, L, 1))
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk",
                                             "interpret"))
def flash_prefill_chunk_pallas(q, k, v, q_offset, *, window: int = 0,
                               bq: int = 512, bk: int = 512,
                               interpret: bool = False):
    """q: [B, Tq, Hq, D] (one segment, rotated at absolute positions
    q_offset..q_offset+Tq); k, v: [B, Tk, Hkv, D] (full prompt scratch).
    q_offset: [1] int32. Returns out [B, Tq, Hq, D]."""
    B, Tq, Hq, D = q.shape
    Tk = k.shape[1]
    Hkv = k.shape[2]
    Gq = Hq // Hkv
    bq, bk = min(bq, Tq), min(bk, Tk)
    assert Tq % bq == 0 and Tk % bk == 0
    qh = q.transpose(0, 2, 1, 3)                   # [B, Hq, Tq, D]
    kh = k.transpose(0, 2, 1, 3)                   # [B, Hkv, Tk, D]
    vh = v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, bq=bq, bk=bk, window=window,
                          scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, Tq // bq, Tk // bk),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, h, i, j, off: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, i, j, off: (b, h // Gq, j, 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, h, i, j, off: (b, h // Gq, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, D),
                                   lambda b, h, i, j, off: (b, h, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Tq, D), q.dtype),
        interpret=interpret,
    )(jnp.asarray(q_offset, jnp.int32).reshape(1), qh, kh, vh)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk",
                                             "interpret"))
def flash_prefill_pallas(q, k, v, *, window: int = 0, bq: int = 512,
                         bk: int = 512, interpret: bool = False):
    """q: [B, Tq, Hq, D]; k, v: [B, Tk, Hkv, D] (Tq == Tk, causal).
    Returns out [B, Tq, Hq, D]."""
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    Gq = Hq // Hkv
    bq, bk = min(bq, T), min(bk, T)
    assert T % bq == 0 and T % bk == 0
    qh = q.transpose(0, 2, 1, 3)                   # [B, Hq, T, D]
    kh = k.transpose(0, 2, 1, 3)                   # [B, Hkv, T, D]
    vh = v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, window=window,
                          scale=1.0 / math.sqrt(D)),
        grid=(B, Hq, T // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // Gq, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // Gq, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
