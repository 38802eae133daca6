"""GQA attention: chunked (flash-style, online over query blocks) for
train/prefill, and cache-aware single-token decode.

The prefill path additionally returns the per-key attention mass — the
heavy-hitter statistic the selective-compression policies consume
(H2O/NACL/Keyformer, survey §2/§4).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cache as kvcache
from repro.core.cache import CacheSpec, LayerKV
from repro.nn import layers as L
from repro.nn.rope import apply_rope

Array = jax.Array
NEG_INF = -1e30

# Canonical query-row group for attention-mass accumulation. Masses are
# folded over fixed MASS_GROUP-row groups *sequentially* (left to right),
# so a prompt processed in one monolithic pass and the same prompt
# processed in chunks accumulate bit-identical totals — float addition
# is not associative, and the chunked-prefill token-equality contract
# (serving/engine.py) needs the same association chain in both paths.
# Chunk starts must be MASS_GROUP-aligned (the engine snaps chunk_len).
MASS_GROUP = 8


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def attn_init(key, cfg, *, cross: bool = False) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    hq = cfg.num_heads * cfg.head_dim
    hkv = cfg.num_kv_heads * cfg.head_dim
    return {
        "wq": L.linear_init(kq, cfg.d_model, hq, bias=cfg.qkv_bias, dtype=cfg.dtype),
        "wk": L.linear_init(kk, cfg.d_model, hkv, bias=cfg.qkv_bias, dtype=cfg.dtype),
        "wv": L.linear_init(kv, cfg.d_model, hkv, bias=cfg.qkv_bias, dtype=cfg.dtype),
        "wo": L.linear_init(ko, hq, cfg.d_model, bias=cfg.attn_out_bias,
                            dtype=cfg.dtype),
    }


def qkv(p: dict, x: Array, cfg, positions: Optional[Array], *, rope: bool = True):
    """x: [B, T, d_model] -> q [B,T,Hq,D], k,v [B,T,Hkv,D] (rotated)."""
    from repro.nn import sharding as shd
    B, T, _ = x.shape
    pq, pk, pv = p["wq"], p["wk"], p["wv"]
    if shd.opt_enabled("weight_gather"):
        pq = {**pq, "w": shd.constrain(pq["w"], None, "tp")}
        pk = {**pk, "w": shd.constrain(pk["w"], None, "tp")}
        pv = {**pv, "w": shd.constrain(pv["w"], None, "tp")}
    q = L.linear(pq, x).reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = L.linear(pk, x).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = L.linear(pv, x).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    if rope:
        if positions is None:
            positions = jnp.arange(T)[None]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    if shd.opt_enabled("kv_replicated"):
        # GQA under tp > kv_heads: keep K/V whole per shard (cheap
        # all-gather) instead of head_dim-sharded (score-sized partial-sum
        # all-reduce in QK^T) — EXPERIMENTS.md §Perf iteration 1.
        q = shd.constrain(q, "fsdp", None, "tp", None)
        k = shd.constrain(k, "fsdp", None, None, None)
        v = shd.constrain(v, "fsdp", None, None, None)
    return q, k, v


# ---------------------------------------------------------------------------
# Dense attention (train / prefill / encoder)
# ---------------------------------------------------------------------------


def _attend_block(q, k, v, mask_bias, scale):
    """q: [B,Tq,Hkv,G,D]; k/v: [B,Tk,Hkv,D]; mask_bias: [B,1,1,Tq,Tk].
    Returns (out, row_mass [B, Tq, Tk]) — per-query-row attention mass,
    reduced over heads only (row-stable: a row's value is independent of
    which other query rows share the block)."""
    s = jnp.einsum("btkgd,bskd->bkgts", q, k).astype(jnp.float32) * scale
    s = s + mask_bias.transpose(0, 1, 2, 3, 4)  # [B,Hkv|1,G|1,Tq,Tk]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v)
    row_mass = p.sum(axis=(1, 2))               # [B, Tq, Tk]
    return o, row_mass


def _fold_mass(carry: Array, row_mass: Array, group: Optional[int]) -> Array:
    """Accumulate per-row masses into `carry` [B, Tk].

    group=None: one reduce over the row axis (legacy single-call path).
    group=g: rows are reduced in g-row blocks and the block partials are
    folded into `carry` strictly left to right (lax.scan — sequential by
    construction). Because the fold continues *from the carry*, a prompt
    split across multiple calls accumulates the exact association chain
    of one big call, provided every call starts on a g-aligned row."""
    B, Tq, Tk = row_mass.shape
    if group is None:
        return carry + row_mass.sum(axis=1)
    pad = (-Tq) % group
    if pad:
        row_mass = jnp.pad(row_mass, ((0, 0), (0, pad), (0, 0)))
    g_mass = row_mass.reshape(B, -1, group, Tk).sum(axis=2)  # [B, nG, Tk]
    carry, _ = jax.lax.scan(lambda c, m: (c + m, None), carry,
                            g_mass.transpose(1, 0, 2))
    return carry


def gqa_attention(
    q: Array, k: Array, v: Array, *,
    causal: bool, window: int = 0,
    q_positions: Optional[Array] = None, kv_positions: Optional[Array] = None,
    kv_bias: Optional[Array] = None, q_chunk: int = 512,
    return_mass: bool = False, mass_group: Optional[int] = None,
    mass_init: Optional[Array] = None, scale: Optional[float] = None,
):
    """General GQA attention.

    q, k: [B, Tq|Tk, Hq|Hkv, D]; v: [B, Tk, Hkv, Dv] (Dv may differ
    from D, as MLA's expanded heads do). scale: softmax scale, default
    1/sqrt(D).
    kv_bias: [B, Tk] additive validity bias.
    Chunked over Tq (flash-style memory profile in pure XLA: scores are
    never materialized beyond [.., q_chunk, Tk]).
    Returns out [B, Tq, Hq, Dv] (+ attention mass [B, Tk] if requested).

    mass_group / mass_init: canonical grouped mass accumulation (see
    `_fold_mass`). `mass_init` seeds the fold — chunked prefill passes
    the running mass so a prompt split across calls accumulates the
    exact association chain of one monolithic call.
    """
    B, Tq, Hq, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Tq, Hkv, G, D)
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Tq)[None], (B, Tq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(k.shape[1])[None],
                                        (B, k.shape[1]))

    def bias_for(qpos_chunk):
        # [B, 1, 1, tq, Tk]
        b = jnp.zeros((B, 1, 1, qpos_chunk.shape[1], kv_positions.shape[1]),
                      jnp.float32)
        rel_ok = jnp.ones_like(b, bool)
        if causal:
            rel_ok &= (kv_positions[:, None, None, None, :]
                       <= qpos_chunk[:, None, None, :, None])
        if window > 0:
            rel_ok &= (kv_positions[:, None, None, None, :]
                       > qpos_chunk[:, None, None, :, None] - window)
        b = jnp.where(rel_ok, 0.0, NEG_INF)
        if kv_bias is not None:
            b = b + kv_bias[:, None, None, None, :]
        return b

    mass0 = (mass_init if mass_init is not None
             else jnp.zeros((B, k.shape[1]), jnp.float32))
    if Tq <= q_chunk:
        o, row_mass = _attend_block(qg, k, v, bias_for(q_positions), scale)
        out = o.reshape(B, Tq, Hq, Dv)
        if not return_mass:
            return out
        return out, _fold_mass(mass0, row_mass, mass_group)

    if Tq % q_chunk:
        # pad queries to a chunk multiple; padded rows are sliced off.
        # (mass accounting assumes divisible Tq — true for all prefill
        # shapes; train masses are unused.)
        assert not return_mass, "return_mass requires Tq % q_chunk == 0"
        pad = q_chunk - Tq % q_chunk
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pp = jnp.pad(q_positions, ((0, 0), (0, pad)), mode="edge")
        out = gqa_attention(qp, k, v, causal=causal, window=window,
                            q_positions=pp, kv_positions=kv_positions,
                            kv_bias=kv_bias, q_chunk=q_chunk, scale=scale)
        return out[:, :Tq]
    n = Tq // q_chunk
    qg_c = qg.reshape(B, n, q_chunk, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
    qpos_c = q_positions.reshape(B, n, q_chunk).transpose(1, 0, 2)

    def body(carry_mass, xs):
        qc, qp = xs
        o, row_mass = _attend_block(qc, k, v, bias_for(qp), scale)
        return _fold_mass(carry_mass, row_mass, mass_group), o

    mass, outs = jax.lax.scan(body, mass0, (qg_c, qpos_c))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Tq, Hq, Dv)
    return (out, mass) if return_mass else out


# ---------------------------------------------------------------------------
# Decode attention over a compressed cache
# ---------------------------------------------------------------------------
#
# Two implementations of the same contract:
#
#   * **materialize oracle** (`use_kernels=False`): `cache.materialize`
#     unpacks + dequantizes the whole main store to the model dtype and
#     concatenates the residual ring, then runs XLA attention. Simple,
#     bit-exact reference — but it moves 16-bit traffic per decode step
#     regardless of `spec.bits`.
#   * **fused Pallas kernel** (`use_kernels=True`): the packed codes are
#     what moves HBM->VMEM (bits/16 of the oracle's bytes); dequant, the
#     residual ring, and the attention-mass statistic are fused into one
#     online-softmax pass (`repro.kernels.decode_qattn`).
#
# `use_kernels=None` defaults to the kernel path on TPU and the oracle
# elsewhere; an explicit True off-TPU runs the kernel in interpret mode
# (slow — for tests / parity checks only).
#
# Both paths also serve the *paged* store (`core.paging.PagedLayerKV`):
# the oracle gathers the slot's blocks into the dense view first, the
# kernel takes the block-table grid variant (`decode_attn_paged_pallas`)
# and walks the block list via scalar-prefetch index maps.


def resolve_use_kernels(flag: Optional[bool]) -> bool:
    if flag is None:
        return jax.default_backend() == "tpu"
    return bool(flag)


def _check_kernel_tiles(lc, spec: CacheSpec) -> None:
    """The fused kernel tiles whole quant groups of a packed store; a
    cache it cannot tile raises rather than quietly taking the oracle,
    so a run that resolved to the kernel never measures the oracle."""
    S = lc.scores.shape[1]
    if spec.quantized and (spec.bits not in (2, 4, 8) or S % spec.group):
        raise ValueError(
            f"the fused decode kernel cannot tile this cache: bits="
            f"{spec.bits}, main store of {S} rows in {spec.group}-row "
            f"groups")


def decode_attention(
    q: Array, lc: LayerKV, spec: CacheSpec, *, window: int = 0,
    dtype=jnp.bfloat16, q_pos: Optional[Array] = None,
    use_kernels: Optional[bool] = None, interpret: Optional[bool] = None,
    layer: Optional[Array] = None,
):
    """q: [B, 1, Hq, D] rotated at absolute position `q_pos` [B]
    (defaults to lc.pos - 1: the append-first decode convention, so the
    token attends to itself through the cache).

    layer: optional int32 scalar — `lc.k`/`lc.v` are the layer-stacked
    dense stores (`cache.append_token_dense`); the kernel attends layer
    `layer` where it lies, the oracle slices it out.

    Returns (out [B, 1, Hq, D], attn_mass [B, S+W]) — mass aligned with
    `cache.materialize` ordering for `cache.accumulate_scores`.
    """
    if q_pos is None:
        q_pos = lc.pos - 1
    paged = not isinstance(lc, LayerKV)      # core.paging.PagedLayerKV
    S = lc.scores.shape[1]
    W = lc.rk.shape[1]
    ring_pos = (lc.pos[:, None] - lc.rlen[:, None] + jnp.arange(W)[None])
    kv_positions = jnp.concatenate([lc.slot_pos, ring_pos.astype(jnp.int32)],
                                   axis=1) if W else lc.slot_pos
    bias = kvcache.validity_bias(lc)
    if window > 0:  # sliding-window models (mixtral): mask stale slots
        in_win = kv_positions > (q_pos[:, None] - window)
        bias = bias + jnp.where(in_win, 0.0, NEG_INF)

    if resolve_use_kernels(use_kernels):
        _check_kernel_tiles(lc, spec)
        from repro.kernels.decode_qattn import ops as dq_ops
        quant = spec.quantized
        # the mass statistic costs a [Gq, S+W] probability scratch and a
        # per-step HBM write — only pay for it when the policy reads it
        want_mass = spec.track_scores()
        if paged:
            # block-table grid: the kernel walks this slot's block list
            # via scalar-prefetch index maps — the pool is never gathered
            out, mass = dq_ops.decode_attention_paged(
                q[:, 0], lc.block_tbl,
                lc.pk, lc.pk_scale if quant else None,
                lc.pk_zero if quant else None,
                lc.pv, lc.pv_scale if quant else None,
                lc.pv_zero if quant else None,
                bias[:, :S],
                lc.rk if W else None, lc.rv if W else None,
                bias[:, S:] if W else None,
                bits=spec.bits if quant else 16, group=spec.group,
                return_mass=want_mass, compute_dtype=dtype,
                interpret=interpret)
        else:
            out, mass = dq_ops.decode_attention_fused(
                q[:, 0],
                lc.k, lc.k_scale if quant else None,
                lc.k_zero if quant else None,
                lc.v, lc.v_scale if quant else None,
                lc.v_zero if quant else None,
                bias[:, :S],
                lc.rk if W else None, lc.rv if W else None,
                bias[:, S:] if W else None,
                bits=spec.bits if quant else 16, group=spec.group,
                return_mass=want_mass, compute_dtype=dtype,
                interpret=interpret, layer=layer)
        if mass is None:
            mass = jnp.zeros((q.shape[0], S + W), jnp.float32)
        return out[:, None].astype(dtype), mass

    if layer is not None:
        lc = lc._replace(k=lc.k[layer], v=lc.v[layer])
    k, v = kvcache.materialize_kv(lc, spec, dtype)
    out, mass = gqa_attention(
        q, k, v, causal=False, kv_positions=kv_positions, kv_bias=bias,
        q_positions=q_pos[:, None], return_mass=True,
    )
    return out, mass


# ---------------------------------------------------------------------------
# Speculative verify: a rectangular segment of queries over the cache
# ---------------------------------------------------------------------------
#
# The draft/verify loop (serving/speculative.py) appends the whole
# speculated segment — the last committed token plus the drafts — via
# `cache.append_segment`, then scores every segment query in ONE pass
# over the cache instead of one decode step per token. Exactness
# argument (the spec-on ≡ spec-off token-equality contract):
#
#   * the speculative engine caps the segment so no eviction and no
#     quantized group flush fires for the *draft* rows (the committed
#     first token may evict/flush — it is never rolled back), so the
#     cache layout after `append_segment` equals the layout sequential
#     decode would see at every sub-step, with the future drafts' rows
#     additionally present;
#   * those future rows are masked per query row by the causal
#     position test below — a masked slot contributes an exact 0.0 to
#     the softmax (max-subtracted exp underflow), so each query row's
#     output and per-key mass are bit-identical to the single-token
#     `decode_attention` it replaces (row-stability of the shared
#     `_attend_block`, the same property the chunked-prefill contract
#     rests on).


def verify_attention(
    q: Array, lc: LayerKV, spec: CacheSpec, *, q_pos: Array,
    window: int = 0, dtype=jnp.bfloat16,
    use_kernels: Optional[bool] = None, interpret: Optional[bool] = None,
):
    """q: [B, L, Hq, D] rotated at absolute positions `q_pos` [B, L];
    the segment's K/V are already appended (append-first convention,
    rows beyond a slot's ragged segment length simply carry stale
    positions the causal test masks).

    Returns (out [B, L, Hq, D], row_mass [B, L, S+W]) — per-query-row
    attention mass aligned with `cache.materialize` ordering, NOT summed
    over rows: the caller accumulates only the accepted rows' masses
    once the draft acceptance length is known.
    """
    B, L, Hq, D = q.shape
    S = lc.scores.shape[1]
    W = lc.rk.shape[1]
    ring_pos = (lc.pos[:, None] - lc.rlen[:, None] + jnp.arange(W)[None])
    # Causal-test positions. Main-store rows carry their true absolute
    # position in `slot_pos`. Ring rows differ by store: a *quantized*
    # ring is the live tail (it holds the segment's own draft rows —
    # its `pos - rlen + arange` labels are true positions and the causal
    # test must apply), while a *dense* ring is frozen at prefill (it
    # holds prefix tokens whose labels drift as `pos` advances — decode
    # runs causal=False over it, so every ring row must stay visible:
    # an impossible-low label keeps the test vacuously true).
    ring_causal = (ring_pos.astype(jnp.int32) if spec.quantized
                   else jnp.full((B, W), -(2 ** 30), jnp.int32))
    causal_pos = (jnp.concatenate([lc.slot_pos, ring_causal], axis=1)
                  if W else lc.slot_pos)
    bias = kvcache.validity_bias(lc)                       # [B, S+W]

    if (resolve_use_kernels(use_kernels) and not spec.track_scores()
            and (window == 0 or spec.quantized)):
        # same dispatch rule as flash prefill: policies that never read
        # the mass statistic take the Pallas segment×cache kernel over
        # the materialized view; mass is reported as zeros there. (A
        # sliding-window model over a dense frozen ring needs two
        # position sets — that combination stays on the oracle.)
        from repro.kernels.flash_prefill import ops as fp_ops
        k, v = kvcache.materialize_kv(lc, spec, dtype)
        out = fp_ops.flash_verify(q, k, v, causal_pos, bias, q_pos,
                                  window=window, interpret=interpret)
        return out.astype(dtype), jnp.zeros((B, L, S + W), jnp.float32)

    # additive per-row bias: validity + causal-by-absolute-position
    # (+ sliding window, which uses decode_attention's drifting ring
    # labels so the two paths mask identically). Adding an exact 0.0
    # where a key is visible keeps the last row's bias bit-identical to
    # `decode_attention`'s.
    ok = causal_pos[:, None, :] <= q_pos[:, :, None]       # [B, L, S+W]
    if window > 0:
        win_pos = (jnp.concatenate(
            [lc.slot_pos, ring_pos.astype(jnp.int32)], axis=1)
            if W else lc.slot_pos)
        ok &= win_pos[:, None, :] > (q_pos[:, :, None] - window)
    full_bias = bias[:, None, :] + jnp.where(ok, 0.0, NEG_INF)

    k, v = kvcache.materialize_kv(lc, spec, dtype)
    Hkv = k.shape[2]
    qg = q.reshape(B, L, Hkv, Hq // Hkv, D)
    out, row_mass = _attend_block(qg, k, v, full_bias[:, None, None],
                                  1.0 / math.sqrt(D))
    return out.reshape(B, L, Hq, D), row_mass
