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
groups ride along in the q block. A dense store is read in its own
`[.., S, Hkv, D]` layout, straight out of the model's layer stack: its
grid is (B, 1, n_main + has_ring), each cell serving every KV head of
its block. Ragged `length`/`rlen` are handled by the additive validity
bias, exactly as on the oracle path.

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

from repro.kernels.blocking import pad_rows, reads_in_place, tile

Array = jax.Array
NEG_INF = -1e30
# VMEM for one buffered (rows, Hkv, D) block of a store read in place: K
# and V are each double-buffered, so four such blocks are live
_ROWS_BLOCK_BYTES = 2 << 20


def _unpack(p: Array, bits: int, D: int) -> Array:
    """int8 [..., D*bits//8] -> int32 codes [..., D]."""
    f = 8 // bits
    x = p.astype(jnp.int32) + 128
    shifts = jnp.arange(f, dtype=jnp.int32) * bits
    mask = (1 << bits) - 1
    codes = (x[..., None] >> shifts) & mask
    return codes.reshape(*p.shape[:-1], D)


def _kernel(*refs, bits: int, D: int, group: int, n_main: int, ring_w: int,
            return_mass: bool, compute_dtype, native: bool, heads: int,
            rows: int):
    """One (batch, kv-head group, cache-block) grid cell.

    Two layouts of the main store and ring. *Transposed* (`native`
    False, one KV head per cell): the caller hands `[.., Hkv, rows, Dp]`
    copies. The TPU compiler takes a block only if its last two dims are
    (8, 128)-aligned or span the array's, so per-key rows ride on a unit
    axis and per-token V scales are columns. *Native* (dense stores):
    K/V are read where they lie, `[L, B, S, Hkv, D]` layer stacks whose
    blocks span `(Hkv, D)`, and one cell serves all `heads` KV heads of
    its block, slicing each head's rows out of the block in VMEM.

      q [1,heads,Gq,D];
      transposed: k [1,1,BS,Dp] (+ k_scale/k_zero [1,1,BS//G,D], v_scale/
      v_zero [1,1,BS,1] when bits<16); v [1,1,BS,Dp];
      native: k/v [1,1,BS,Hkv,D];
      bias_main [1,1,1,BS];
      ring (ring_w>0): rk/rv [1,1,W,D] transposed | [1,W,Hkv,D] native,
      bias_ring [1,1,W];
      out o [1,heads,Gq,D] (+ mass [1,heads,n_main,BS], and ring mass
      [1,heads,1,W] when ring_w>0, when return_mass);
      scratch m/l [heads,Gq,1], acc [heads,Gq,D] (+ p [heads,n_main,Gq,BS],
      and ring p [heads,Gq,W] when ring_w>0, when return_mass).

    `rows` (native only, else 0): the store's length when its last block
    overhangs it. The bias masks those rows, but an overhanging block's
    tail is undefined, and 0 * NaN is NaN: they are zeroed.
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

    scale = 1.0 / math.sqrt(D)

    def attend(h, k, v, bias_row):
        """Online-softmax update of KV head `h` for one key block
        [width, D]; returns the block's probabilities relative to the
        new running max."""
        q = q_ref[0, h].astype(jnp.float32)                  # [Gq, D]
        s = (q @ k.T) * scale + bias_row                     # [Gq, width]
        m_prev = m_scr[h]                                    # [Gq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # [Gq, width]
        l_scr[h] = l_scr[h] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + p @ v
        m_scr[h] = m_new
        if return_mass:
            # stored probabilities stay relative to the *current* max:
            # rescale history; the caller drops in the fresh block
            p_scr[h] = p_scr[h] * alpha[None]
            if ring_w:
                pr_scr[h] = pr_scr[h] * alpha
        return p

    def main_kv(h):
        if native:
            k = k_ref[0, 0, :, h, :].astype(jnp.float32)
            v = v_ref[0, 0, :, h, :].astype(jnp.float32)
            if rows:
                bs = k.shape[0]
                live = (s_idx * bs + jax.lax.broadcasted_iota(
                    jnp.int32, (bs, 1), 0)) < rows
                k = jnp.where(live, k, 0.0)
                v = jnp.where(live, v, 0.0)
            return k, v
        if bits < 16:
            kc = _unpack(k_ref[0, 0], bits, D).astype(jnp.float32)
            ks = jnp.repeat(ks_ref[0, 0], group, axis=0)     # [BS, D]
            kz = jnp.repeat(kz_ref[0, 0], group, axis=0)
            k = ((kc * ks + kz).astype(compute_dtype)
                 .astype(jnp.float32))
            vc = _unpack(v_ref[0, 0], bits, D).astype(jnp.float32)
            v = ((vc * vs_ref[0, 0] + vz_ref[0, 0])
                 .astype(compute_dtype).astype(jnp.float32))
            return k, v
        return (k_ref[0, 0].astype(jnp.float32),
                v_ref[0, 0].astype(jnp.float32))

    @pl.when(s_idx < n_main)
    def _main_block():
        for h in range(heads):
            k, v = main_kv(h)
            p = attend(h, k, v, biasm_ref[0, 0])
            if return_mass:
                p_scr[h, s_idx] = p

    if ring_w:
        @pl.when(s_idx == n_main)
        def _ring_block():
            for h in range(heads):
                if native:
                    k, v = rk_ref[0, :, h, :], rv_ref[0, :, h, :]
                else:
                    k, v = rk_ref[0, 0], rv_ref[0, 0]
                p = attend(h, k.astype(jnp.float32), v.astype(jnp.float32),
                           biasr_ref[0])
                if return_mass:
                    pr_scr[h] = p

    @pl.when(s_idx == total - 1)
    def _done():
        for h in range(heads):
            l = jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)
            if return_mass:
                mass_ref[0, h] = (p_scr[h] / l[None]).sum(axis=1)
                if ring_w:
                    rmass_ref[0, h] = (pr_scr[h] / l).sum(axis=0,
                                                          keepdims=True)


def _call(B, Hkv, Gq, D, n_main, bs, W, q_dtype, *, bits, group,
          return_mass, compute_dtype, interpret, idx, prefetch=0,
          native=False, rows=0):
    """Shared pallas_call assembly of the dense, stacked and block-table
    grids.

    `idx(kind)` returns the index map of a main-store operand of `kind`
    ("kv" for K/V/K-scale blocks, "bias" for the [B, n, 1, BS] row);
    everything else is indexed by (batch, kv-head) alone. Index maps take
    `prefetch` trailing scalar-prefetch refs. `native` reads K/V and the
    ring in the store's own layout (see `_kernel`): the grid's head axis
    is then 1 and each cell serves every head. Returns the configured
    callable."""
    heads = Hkv if native else 1

    def fixed(*tail):
        def f(b, h, s, *_):
            return (b, h) + tail
        return f

    if native:
        kv_spec = pl.BlockSpec((1, 1, bs, Hkv, D), idx("kv"))
        ring_spec = pl.BlockSpec((1, W, Hkv, D),
                                 lambda b, h, s, *_: (b, 0, 0, 0))
    else:
        kv_spec = pl.BlockSpec((1, 1, bs, D * bits // 8 if bits < 16 else D),
                               idx("kv"))
        ring_spec = pl.BlockSpec((1, 1, W, D), fixed(0, 0))
    in_specs = [pl.BlockSpec((1, heads, Gq, D), fixed(0, 0)), kv_spec]
    if bits < 16:
        in_specs += [pl.BlockSpec((1, 1, bs // group, D), idx("kv"))] * 2
    in_specs.append(kv_spec)
    if bits < 16:
        in_specs += [pl.BlockSpec((1, 1, bs, 1), idx("kv"))] * 2
    in_specs.append(pl.BlockSpec((1, 1, 1, bs), idx("bias")))
    if W:
        in_specs += [ring_spec] * 2
        in_specs.append(pl.BlockSpec((1, 1, W), lambda b, h, s, *_: (b, 0, 0)))

    out_shape = [jax.ShapeDtypeStruct((B, Hkv, Gq, D), q_dtype)]
    out_specs = [pl.BlockSpec((1, heads, Gq, D), fixed(0, 0))]
    scratch = [pltpu.VMEM((heads, Gq, 1), jnp.float32),
               pltpu.VMEM((heads, Gq, 1), jnp.float32),
               pltpu.VMEM((heads, Gq, D), jnp.float32)]
    if return_mass:
        out_shape.append(jax.ShapeDtypeStruct((B, Hkv, n_main, bs),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, heads, n_main, bs), fixed(0, 0)))
        scratch.append(pltpu.VMEM((heads, n_main, Gq, bs), jnp.float32))
        if W:
            out_shape.append(jax.ShapeDtypeStruct((B, Hkv, 1, W),
                                                  jnp.float32))
            out_specs.append(pl.BlockSpec((1, heads, 1, W), fixed(0, 0)))
            scratch.append(pltpu.VMEM((heads, Gq, W), jnp.float32))

    body = functools.partial(_kernel, bits=bits, D=D, group=group,
                             n_main=n_main, ring_w=W,
                             return_mass=return_mass,
                             compute_dtype=compute_dtype, native=native,
                             heads=heads, rows=rows)

    def kernel(*refs):
        # scalar-prefetch refs are only consumed by the index maps
        body(*refs[prefetch:])

    grid = (B, Hkv // heads, n_main + (1 if W else 0))
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


def _side_operands(q, rk, rv, bias_ring, Hkv, native=False):
    """q as [B, Hkv, Gq, D] and the ring pieces in kernel layout (the
    native layout takes the ring as it lies)."""
    B, Hq, D = q.shape
    ops = [q.reshape(B, Hkv, Hq // Hkv, D)]
    ring = []
    if rk is not None:
        if not native:
            rk, rv = rk.transpose(0, 2, 1, 3), rv.transpose(0, 2, 1, 3)
        ring = [rk, rv, bias_ring.reshape(B, 1, -1)]
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
                       compute_dtype=jnp.float32, interpret: bool = False,
                       layer=None):
    """Fused decode attention over [main store | residual ring].

    q: [B, Hq, D].
    Main store (bits < 16): k/v [B, S, Hkv, D*bits/8] int8 packed codes,
    k_scale/k_zero [B, S//group, Hkv, D], v_scale/v_zero [B, S, Hkv];
    (bits == 16): k/v [B, S, Hkv, D] dense, scales/zeros None; or, with
    `layer` (an int32 scalar, traced), the layer-stacked stores
    [L, B, S, Hkv, D], of which layer `layer` is attended.
    bias_main: [B, S] additive validity/window bias.
    Ring (optional): rk/rv [B, W, Hkv, D] full precision, bias_ring
    [B, W]; pass None/None/None for W == 0.

    A layer stack, and a dense store whose layout allows it
    (`blocking.reads_in_place`), is read where it lies: its blocks span
    `(Hkv, D)` and at most `_ROWS_BLOCK_BYTES`, the layer index rides in
    as a scalar-prefetch operand, and nothing is copied, transposed or
    padded (a last block that overhangs the store is masked). Any other
    store is transposed to `[B, Hkv, S, Dp]`; its block is a whole
    number of quant groups whose K scales fill whole sublanes
    (`blocking.tile`), and a store that no such block divides is padded
    here with masked rows.

    Returns (out [B, Hq, D] in q.dtype,
             mass [B, S+W] f32 if return_mass else None) with `mass`
    aligned to `cache.materialize` / `cache.accumulate_scores` ordering.
    """
    B, Hq, D = q.shape
    S, Hkv = k.shape[-3], k.shape[-2]
    W = rk.shape[1] if rk is not None else 0
    native = layer is not None or (bits == 16 and reads_in_place(Hkv, D))
    if native:
        assert bits == 16, "only a dense store is read where it lies"
        row = -(-Hkv // 8) * 8 * D * k.dtype.itemsize   # in VMEM tiles
        block_s = min(block_s, max(8, _ROWS_BLOCK_BYTES // row))
    bs, S_pad = tile(S, 8 * group if bits < 16 else 8, block_s)
    n_main = S_pad // bs
    pad = S_pad - S

    operands, ring = _side_operands(q, rk, rv, bias_ring, Hkv, native)
    if native:
        if layer is None:                     # one layer: a stack of one
            k, v, layer = k[None], v[None], 0
        prefetch = [jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))]
        operands += [k, v]
    else:
        prefetch = []
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
            return lambda b, h, s, *_: (b, jnp.minimum(s, n_main - 1), 0, 0)
        if native:
            return lambda b, h, s, lay: (lay[0], b, jnp.minimum(s, n_main - 1),
                                         0, 0)
        return lambda b, h, s: (b, h, jnp.minimum(s, n_main - 1), 0)

    call = _call(B, Hkv, Hq // Hkv, D, n_main, bs, W, q.dtype, bits=bits,
                 group=group, return_mass=return_mass,
                 compute_dtype=compute_dtype, interpret=interpret, idx=idx,
                 prefetch=len(prefetch), native=native,
                 rows=S if native and pad else 0)
    return _finish(call(*prefetch, *operands, *ring), B, Hq, D, S, W,
                   return_mass)


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
