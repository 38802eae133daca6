"""Fused flash-decode attention over the *compressed* KV cache.

The survey's quantization systems (KVQuant [15], KIVI [17]) win because
the decode step is HBM-bandwidth-bound: attention reads the whole cache
per token. Their CUDA kernels fuse dequantization into the attention
load. TPU adaptation (DESIGN.md §2): the packed int codes are what moves
HBM->VMEM (bits/16 of the bf16 traffic); unpack+dequant happens in
VREGs right after the copy; QK^T and PV run on the MXU per cache block;
online-softmax accumulators live in VMEM scratch across the sequential
cache-block grid axis.

This kernel is the real decode path of the model (see
`repro.nn.attention.decode_attention`), so it covers everything the
`cache.materialize` oracle provides:

  * **quantized main store** (bits ∈ {2, 4, 8}): packed int8 codes +
    per-channel K scales (KIVI layout), dequantized in-kernel;
  * **dense main store** (bits == 16): a plain bf16 flash-decode branch,
    so selective-only caches get the fused path too;
  * **residual ring**: the full-precision recent window is attended as a
    trailing grid block inside the same online-softmax pass — no concat,
    no materialization;
  * **attention mass** (optional): the per-key probability column sums
    `[B, S+W]` that H2O/NACL/Keyformer score accumulation consumes,
    assembled from a per-(kv-head) probability scratch that is rescaled
    as the running max moves.

Grid: (B, Hkv, n_main + has_ring) — the cache-block axis is innermost
and sequential, so scratch accumulators carry across it; GQA query
groups ride along in the q block. Ragged `length`/`rlen` are handled by
the additive validity bias, exactly as on the oracle path.

`compute_dtype` mirrors the oracle's precision: `materialize`
dequantizes to the model dtype before the matmuls, so the kernel rounds
its dequantized K/V through the same dtype to stay bit-near the
reference (pass float32 to skip the rounding).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.blocking import pad_rows, tile

Array = jax.Array
NEG_INF = -1e30


def _unpack(p: Array, bits: int, D: int) -> Array:
    """int8 [..., D*bits//8] -> int32 codes [..., D]."""
    f = 8 // bits
    x = p.astype(jnp.int32) + 128
    shifts = jnp.arange(f, dtype=jnp.int32) * bits
    mask = (1 << bits) - 1
    codes = (x[..., None] >> shifts) & mask
    return codes.reshape(*p.shape[:-1], D)


def _kernel(*refs, bits: int, D: int, group: int, n_main: int, ring_w: int,
            return_mass: bool, compute_dtype):
    """One (batch, kv-head, cache-block) grid cell.

    Ref layout (inputs, then outputs, then scratch — pieces that are
    statically absent simply aren't passed). The TPU compiler takes a
    block only if its last two dims are (8, 128)-aligned or span the
    array's, so per-key rows ride on a unit axis and per-token V scales
    are columns:

      q [1,1,Gq,D];
      k [1,1,BS,Dp] (+ k_scale/k_zero [1,1,BS//G,D], v_scale/v_zero
      [1,1,BS,1] when bits<16); v [1,1,BS,Dp]; bias_main [1,1,1,BS];
      ring: rk/rv [1,1,W,D] + bias_ring [1,1,W] when ring_w>0;
      out o [1,1,Gq,D] (+ mass [1,1,n_main,BS], and ring mass [1,1,1,W]
      when ring_w>0, when return_mass);
      scratch m/l [Gq,1], acc [Gq,D] (+ p [n_main,Gq,BS], and ring p
      [Gq,W] when ring_w>0, when return_mass).
    """
    it = iter(refs)
    q_ref = next(it)
    k_ref = next(it)
    if bits < 16:
        ks_ref, kz_ref = next(it), next(it)
    v_ref = next(it)
    if bits < 16:
        vs_ref, vz_ref = next(it), next(it)
    biasm_ref = next(it)
    if ring_w:
        rk_ref, rv_ref, biasr_ref = next(it), next(it), next(it)
    o_ref = next(it)
    if return_mass:
        mass_ref = next(it)
        rmass_ref = next(it) if ring_w else None
    m_scr, l_scr, acc_scr = next(it), next(it), next(it)
    if return_mass:
        p_scr = next(it)
        pr_scr = next(it) if ring_w else None

    s_idx = pl.program_id(2)
    total = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if return_mass:
            p_scr[...] = jnp.zeros_like(p_scr)
            if ring_w:
                pr_scr[...] = jnp.zeros_like(pr_scr)

    q = q_ref[0, 0].astype(jnp.float32)                      # [Gq, D]
    scale = 1.0 / math.sqrt(D)

    def attend(k, v, bias_row):
        """Online-softmax update for one key block [width, D]; returns
        the block's probabilities relative to the new running max."""
        s = (q @ k.T) * scale + bias_row                     # [Gq, width]
        m_prev = m_scr[...]                                  # [Gq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # [Gq, width]
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + p @ v
        m_scr[...] = m_new
        if return_mass:
            # stored probabilities stay relative to the *current* max:
            # rescale history; the caller drops in the fresh block
            p_scr[...] = p_scr[...] * alpha[None]
            if ring_w:
                pr_scr[...] = pr_scr[...] * alpha
        return p

    @pl.when(s_idx < n_main)
    def _main_block():
        if bits < 16:
            kc = _unpack(k_ref[0, 0], bits, D).astype(jnp.float32)
            ks = jnp.repeat(ks_ref[0, 0], group, axis=0)     # [BS, D]
            kz = jnp.repeat(kz_ref[0, 0], group, axis=0)
            k = ((kc * ks + kz).astype(compute_dtype)
                 .astype(jnp.float32))
            vc = _unpack(v_ref[0, 0], bits, D).astype(jnp.float32)
            v = ((vc * vs_ref[0, 0] + vz_ref[0, 0])
                 .astype(compute_dtype).astype(jnp.float32))
        else:
            k = k_ref[0, 0].astype(jnp.float32)
            v = v_ref[0, 0].astype(jnp.float32)
        p = attend(k, v, biasm_ref[0, 0])
        if return_mass:
            p_scr[s_idx] = p

    if ring_w:
        @pl.when(s_idx == n_main)
        def _ring_block():
            k = rk_ref[0, 0].astype(jnp.float32)
            v = rv_ref[0, 0].astype(jnp.float32)
            p = attend(k, v, biasr_ref[0])
            if return_mass:
                pr_scr[...] = p

    @pl.when(s_idx == total - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if return_mass:
            mass_ref[0, 0] = (p_scr[...] / l[None]).sum(axis=1)
            if ring_w:
                rmass_ref[0, 0] = (pr_scr[...] / l).sum(axis=0,
                                                         keepdims=True)


def _call(B, Hkv, Gq, D, n_main, bs, W, q_dtype, *, bits, group,
          return_mass, compute_dtype, interpret, idx, prefetch=0):
    """Shared pallas_call assembly of the dense and block-table grids.

    `idx(kind)` returns the index map of a main-store operand of `kind`
    ("kv" for 4-d K/V/K-scale blocks, "bias" for the [B, n, 1, BS] row);
    everything else is indexed by (batch, kv-head) alone. Index maps take
    `prefetch` trailing scalar-prefetch refs. Returns the configured
    callable and the in-spec list the caller zips with its operands."""
    def fixed(*tail):
        def f(b, h, s, *_):
            return (b, h) + tail
        return f

    in_specs = [pl.BlockSpec((1, 1, Gq, D), fixed(0, 0)),
                pl.BlockSpec((1, 1, bs, D * bits // 8 if bits < 16 else D),
                             idx("kv"))]
    if bits < 16:
        in_specs += [pl.BlockSpec((1, 1, bs // group, D), idx("kv"))] * 2
    in_specs.append(in_specs[1])
    if bits < 16:
        in_specs += [pl.BlockSpec((1, 1, bs, 1), idx("kv"))] * 2
    in_specs.append(pl.BlockSpec((1, 1, 1, bs), idx("bias")))
    if W:
        in_specs += [pl.BlockSpec((1, 1, W, D), fixed(0, 0))] * 2
        in_specs.append(pl.BlockSpec((1, 1, W), lambda b, h, s, *_: (b, 0, 0)))

    out_shape = [jax.ShapeDtypeStruct((B, Hkv, Gq, D), q_dtype)]
    out_specs = [pl.BlockSpec((1, 1, Gq, D), fixed(0, 0))]
    scratch = [pltpu.VMEM((Gq, 1), jnp.float32),
               pltpu.VMEM((Gq, 1), jnp.float32),
               pltpu.VMEM((Gq, D), jnp.float32)]
    if return_mass:
        out_shape.append(jax.ShapeDtypeStruct((B, Hkv, n_main, bs),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, n_main, bs), fixed(0, 0)))
        scratch.append(pltpu.VMEM((n_main, Gq, bs), jnp.float32))
        if W:
            out_shape.append(jax.ShapeDtypeStruct((B, Hkv, 1, W),
                                                  jnp.float32))
            out_specs.append(pl.BlockSpec((1, 1, 1, W), fixed(0, 0)))
            scratch.append(pltpu.VMEM((Gq, W), jnp.float32))

    body = functools.partial(_kernel, bits=bits, D=D, group=group,
                             n_main=n_main, ring_w=W,
                             return_mass=return_mass,
                             compute_dtype=compute_dtype)

    def kernel(*refs):
        # scalar-prefetch refs are only consumed by the index maps
        body(*refs[prefetch:])

    grid = (B, Hkv, n_main + (1 if W else 0))
    if prefetch:
        call = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=prefetch, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape, interpret=interpret)
    else:
        call = pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret)
    return call


def _side_operands(q, rk, rv, bias_ring, Hkv):
    """q as [B, Hkv, Gq, D] and the ring pieces in kernel layout."""
    B, Hq, D = q.shape
    ops = [q.reshape(B, Hkv, Hq // Hkv, D)]
    ring = []
    if rk is not None:
        ring = [rk.transpose(0, 2, 1, 3), rv.transpose(0, 2, 1, 3),
                bias_ring.reshape(B, 1, -1)]
    return ops, ring


def _finish(outs, B, Hq, D, S, W, return_mass):
    """(out [B, Hq, D], mass [B, S+W] | None) from the kernel outputs."""
    out = outs[0].reshape(B, Hq, D)
    if not return_mass:
        return out, None
    mass = outs[1].reshape(B, outs[1].shape[1], -1)[..., :S]
    if W:
        mass = jnp.concatenate([mass, outs[2][:, :, 0]], axis=-1)
    return out, mass.sum(axis=1)             # sum over kv heads -> [B, S+W]


@functools.partial(jax.jit, static_argnames=("bits", "group", "block_s",
                                             "return_mass", "compute_dtype",
                                             "interpret"))
def decode_attn_pallas(q, k, k_scale, k_zero, v, v_scale, v_zero, bias_main,
                       rk, rv, bias_ring, *, bits: int, group: int,
                       block_s: int = 512, return_mass: bool = False,
                       compute_dtype=jnp.float32, interpret: bool = False):
    """Fused decode attention over [main store | residual ring].

    q: [B, Hq, D].
    Main store (bits < 16): k/v [B, S, Hkv, D*bits/8] int8 packed codes,
    k_scale/k_zero [B, S//group, Hkv, D], v_scale/v_zero [B, S, Hkv];
    (bits == 16): k/v [B, S, Hkv, D] dense, scales/zeros None.
    bias_main: [B, S] additive validity/window bias.
    Ring (optional): rk/rv [B, W, Hkv, D] full precision, bias_ring
    [B, W]; pass None/None/None for W == 0.

    The cache block is a whole number of quant groups whose K scales
    fill whole sublanes (`blocking.tile`); a store that no such block
    divides is padded here with masked rows.

    Returns (out [B, Hq, D] in q.dtype,
             mass [B, S+W] f32 if return_mass else None) with `mass`
    aligned to `cache.materialize` / `cache.accumulate_scores` ordering.
    """
    B, Hq, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    W = rk.shape[1] if rk is not None else 0
    bs, S_pad = tile(S, 8 * group if bits < 16 else 8, block_s)
    n_main = S_pad // bs
    pad = S_pad - S

    operands, ring = _side_operands(q, rk, rv, bias_ring, Hkv)
    operands.append(pad_rows(k, pad).transpose(0, 2, 1, 3))  # [B,Hkv,S,Dp]
    if bits < 16:
        operands += [pad_rows(x, pad // group).transpose(0, 2, 1, 3)
                     for x in (k_scale, k_zero)]
    operands.append(pad_rows(v, pad).transpose(0, 2, 1, 3))
    if bits < 16:
        operands += [pad_rows(x, pad).transpose(0, 2, 1)[..., None]
                     for x in (v_scale, v_zero)]
    operands.append(pad_rows(bias_main, pad, NEG_INF).reshape(B, n_main, 1,
                                                              bs))

    def idx(kind):
        if kind == "bias":
            return lambda b, h, s: (b, jnp.minimum(s, n_main - 1), 0, 0)
        return lambda b, h, s: (b, h, jnp.minimum(s, n_main - 1), 0)

    call = _call(B, Hkv, Hq // Hkv, D, n_main, bs, W, q.dtype, bits=bits,
                 group=group, return_mass=return_mass,
                 compute_dtype=compute_dtype, interpret=interpret, idx=idx)
    return _finish(call(*operands, *ring), B, Hq, D, S, W, return_mass)


@functools.partial(jax.jit, static_argnames=("bits", "group", "return_mass",
                                             "compute_dtype", "interpret"))
def decode_attn_paged_pallas(q, block_tbl, pk, pk_scale, pk_zero, pv,
                             pv_scale, pv_zero, bias_main, rk, rv, bias_ring,
                             *, bits: int, group: int,
                             return_mass: bool = False,
                             compute_dtype=jnp.float32,
                             interpret: bool = False):
    """Block-table grid variant: walk each slot's block list.

    Same online-softmax body as `decode_attn_pallas`; the only change is
    *where the key blocks come from*. The main-store operands are shared
    block **pools** with no batch dim — `[n_blocks, bl, Hkv, Dp]` codes
    (+ `[n_blocks, bl//group, Hkv, D]` K scales and `[n_blocks, bl, Hkv]`
    V scales when bits < 16) — and `block_tbl [B, n_max]` rides in as a
    scalar-prefetch operand so the BlockSpec index maps can chase it:
    grid step (b, h, s) DMAs pool block ``block_tbl[b, s]``. Unmapped
    entries (-1) are clamped to block 0 here; the `bias_main
    [B, n_max*bl]` validity bias masks those positions, so the clamped
    reads never contribute. A pool block spans its array's trailing dims,
    so any block length is legal on the chip.

    q [B, Hq, D]; ring/bias/out exactly as `decode_attn_pallas`.
    Returns (out [B, Hq, D], mass [B, S+W] | None)."""
    B, Hq, D = q.shape
    bl, Hkv = pk.shape[1], pk.shape[2]
    n_max = block_tbl.shape[1]
    S = n_max * bl
    assert bias_main.shape == (B, S), (bias_main.shape, B, S)
    if bits < 16:
        assert bl % group == 0, (bl, group)
    W = rk.shape[1] if rk is not None else 0

    operands, ring = _side_operands(q, rk, rv, bias_ring, Hkv)
    operands.append(pk.transpose(0, 2, 1, 3))         # [nb, Hkv, bl, Dp]
    if bits < 16:
        operands += [x.transpose(0, 2, 1, 3) for x in (pk_scale, pk_zero)]
    operands.append(pv.transpose(0, 2, 1, 3))
    if bits < 16:
        operands += [x.transpose(0, 2, 1)[..., None]
                     for x in (pv_scale, pv_zero)]
    operands.append(bias_main.reshape(B, n_max, 1, bl))
    tbl = jnp.maximum(block_tbl, 0).astype(jnp.int32)

    def idx(kind):
        if kind == "bias":
            return lambda b, h, s, t: (b, jnp.minimum(s, n_max - 1), 0, 0)
        return lambda b, h, s, t: (t[b, jnp.minimum(s, n_max - 1)], h, 0, 0)

    call = _call(B, Hkv, Hq // Hkv, D, n_max, bl, W, q.dtype, bits=bits,
                 group=group, return_mass=return_mass,
                 compute_dtype=compute_dtype, interpret=interpret, idx=idx,
                 prefetch=1)
    return _finish(call(tbl, *operands, *ring), B, Hq, D, S, W, return_mass)


@functools.partial(jax.jit, static_argnames=("bits", "group", "block_s",
                                             "interpret"))
def decode_qattn_pallas(q, kq, ks, kz, vq, vs, vz, bias, *, bits: int,
                        group: int, block_s: int = 512,
                        interpret: bool = False):
    """Back-compat wrapper: quantized main store only, no ring, no mass.

    q: [B, Hq, D]; kq/vq: [B, S, Hkv, Dp] int8; ks/kz: [B, S//G, Hkv, D];
    vs/vz: [B, S, Hkv]; bias: [B, S]. Returns out [B, Hq, D] (q.dtype)."""
    out, _ = decode_attn_pallas(
        q, kq, ks, kz, vq, vs, vz, bias, None, None, None, bits=bits,
        group=group, block_s=block_s, return_mass=False,
        compute_dtype=jnp.float32, interpret=interpret)
    return out
