"""Decoder/encoder blocks: (attention | Mamba2) mixer + (dense | MoE) FFN,
pre-norm residual. Blocks are pure functions over param dicts; the model
stacks them into superblocks and scans (see `repro.nn.model`)."""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import cache as kvcache
from repro.core.cache import CacheSpec, LayerKV, SSMState
from repro.nn import attention as attn
from repro.nn import layers as L
from repro.nn import mla
from repro.nn import moe as moe_lib
from repro.nn import ssm as ssm_lib

Array = jax.Array


class BlockAux(NamedTuple):
    lb_loss: Array
    z_loss: Array


ZERO_AUX = BlockAux(jnp.zeros(()), jnp.zeros(()))


def block_init(key, cfg, kind: str, ffn_kind: str, *, cross: bool = False) -> dict:
    ks = jax.random.split(key, 8)
    p: dict = {"norm1": L.rmsnorm_init(cfg.d_model, cfg.dtype)}
    if kind == "attn":
        p["attn"] = (mla.mla_init(ks[0], cfg) if cfg.is_mla
                     else attn.attn_init(ks[0], cfg))
    else:
        p["ssm"] = ssm_lib.ssm_init(ks[1], cfg)
    if cfg.d_ff > 0 or (ffn_kind == "moe"):
        p["norm2"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
        if ffn_kind == "moe":
            p["moe"] = moe_lib.moe_init(ks[2], cfg.d_model, cfg.moe.d_expert,
                                        cfg.moe.num_experts, cfg.dtype,
                                        num_shared=cfg.moe.num_shared)
        else:
            p["mlp"] = L.mlp_init(ks[3], cfg.d_model, cfg.d_ff,
                                  bias=cfg.mlp_bias, dtype=cfg.dtype)
    if cross:
        p["norm_x"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
        p["xattn"] = attn.attn_init(ks[4], cfg)
    return p


def _ffn(p: dict, x: Array, cfg) -> tuple[Array, BlockAux]:
    if "moe" in p:
        e = cfg.moe
        y, aux = moe_lib.moe_apply(p["moe"], L.rmsnorm(p["norm2"], x, cfg.norm_eps),
                                   top_k=e.num_experts_per_tok,
                                   capacity_factor=e.capacity_factor,
                                   norm_topk=e.norm_topk)
        return x + y, BlockAux(aux.load_balance_loss, aux.router_z_loss)
    if "mlp" in p:
        return x + L.mlp(p["mlp"], L.rmsnorm(p["norm2"], x, cfg.norm_eps)), ZERO_AUX
    return x, ZERO_AUX


def _ffn_serve(p: dict, x: Array, cfg, live: Optional[Array] = None):
    """The FFN of the serving paths: MoE layers route drop-free, so a
    token's output depends on that token alone. `live` ([B, T] bool or
    None) marks the rows the MoE counters count. Returns (x, this
    call's `moe.MoEStats`, or None for a dense layer)."""
    if "moe" not in p:
        return _ffn(p, x, cfg)[0], None
    e = cfg.moe
    y, st = moe_lib.moe_apply_dropfree(
        p["moe"], L.rmsnorm(p["norm2"], x, cfg.norm_eps),
        top_k=e.num_experts_per_tok, norm_topk=e.norm_topk,
        use_kernel=attn.resolve_use_kernels(getattr(cfg, "use_kernels",
                                                    None)), live=live)
    return x + y, st


def _cross_attend(p: dict, x: Array, memory_kv, cfg) -> Array:
    """memory_kv: (k, v, bias) precomputed from encoder output."""
    if "xattn" not in p or memory_kv is None:
        return x
    mk, mv, mbias = memory_kv
    h = L.rmsnorm(p["norm_x"], x, cfg.norm_eps)
    B, T, _ = h.shape
    q = L.linear(p["xattn"]["wq"], h).reshape(B, T, cfg.num_heads, cfg.head_dim)
    o = attn.gqa_attention(q, mk, mv, causal=False, kv_bias=mbias)
    return x + L.linear(p["xattn"]["wo"], o.reshape(B, T, -1))


def cross_kv(p: dict, memory: Array, cfg):
    """Precompute cross-attention K/V from encoder output [B, Ts, d]."""
    B, Ts, _ = memory.shape
    k = L.linear(p["xattn"]["wk"], memory).reshape(B, Ts, cfg.num_kv_heads,
                                                   cfg.head_dim)
    v = L.linear(p["xattn"]["wv"], memory).reshape(B, Ts, cfg.num_kv_heads,
                                                   cfg.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# Full-sequence forward (training / encoder)
# ---------------------------------------------------------------------------


def _use_flash_prefill(cfg, causal: bool, positions) -> bool:
    """Dense full-sequence attention via the Pallas flash kernel —
    **inference prefill only** (`block_prefill`): pallas_call has no AD
    rule, so the differentiable training forward (`block_train`) must
    stay on XLA attention. The kernel derives its causal/window mask
    purely from block offsets (0-based arange), so it is only legal when
    the caller left `positions=None` — the standard-arange default.
    Callers with custom positions (offset prefills, packing) stay on the
    XLA path."""
    return (causal and positions is None
            and attn.resolve_use_kernels(getattr(cfg, "use_kernels", None)))


def block_train(p: dict, x: Array, cfg, kind: str, *,
                positions: Optional[Array] = None, causal: bool = True,
                memory_kv=None) -> tuple[Array, BlockAux]:
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        q, k, v = attn.qkv(p["attn"], h, cfg, positions)
        # no kernel dispatch here: block_train runs under value_and_grad
        # and pallas_call is not differentiable (see _use_flash_prefill)
        o = attn.gqa_attention(q, k, v, causal=causal,
                               window=cfg.sliding_window,
                               q_positions=positions, kv_positions=positions)
        # (§Perf iteration 3, REFUTED: constraining o to 16-way head
        # sharding doubled compute via 40->48 head padding; GSPMD's own
        # 8-way choice is better. Hook removed — see EXPERIMENTS.md §Perf.)
        B, T, _ = x.shape
        x = x + L.linear(p["attn"]["wo"], o.reshape(B, T, -1))
    else:
        o, _ = ssm_lib.mamba2_forward(p["ssm"], h, cfg)
        x = x + o
    x = _cross_attend(p, x, memory_kv, cfg)
    return _ffn(p, x, cfg)


# ---------------------------------------------------------------------------
# Prefill: forward + build the compressed cache for this layer
# ---------------------------------------------------------------------------


def block_prefill(p: dict, x: Array, cfg, kind: str, spec: CacheSpec, *,
                  positions: Optional[Array] = None,
                  logical_budget: Optional[Array] = None,
                  key: Optional[Array] = None, memory_kv=None):
    """Returns (x, MoE stats or None, LayerKV | SSMState)."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind == "attn" and cfg.is_mla:
        # expanded form over the prompt's own latent rows; the store
        # keeps the rows alone (see nn/mla.py)
        pa = p["attn"]
        rows = mla.latent_rows(pa, h, cfg, positions)
        q_nope, q_pe = mla.queries(pa, h, cfg, positions)
        o = mla.attend_expanded(pa, q_nope, q_pe, rows, cfg, positions)
        x = x + L.linear(pa["wo"], o)
        lc = kvcache.compress_prompt(
            spec, rows, rows[..., :0],
            jnp.zeros(x.shape[:2], jnp.float32), key=key, dtype=cfg.dtype,
            logical_budget=logical_budget)
        x, st = _ffn_serve(p, x, cfg)
        return x, st, lc
    if kind == "attn":
        q, k, v = attn.qkv(p["attn"], h, cfg, positions)
        if _use_flash_prefill(cfg, True, positions) and not spec.track_scores():
            # policies that never read the mass statistic (streaming /
            # quantized-only) take the flash kernel; compress_prompt's
            # selection uses recency for these, so zero mass is exact.
            from repro.kernels.flash_prefill import ops as fp_ops
            o = fp_ops.flash_attention(q, k, v, window=cfg.sliding_window)
            mass = jnp.zeros(x.shape[:2], jnp.float32)
        else:
            # mass_group: canonical sequential fold so a chunked prefill
            # (block_prefill_chunk) accumulates bit-identical totals
            o, mass = attn.gqa_attention(
                q, k, v, causal=True, window=cfg.sliding_window,
                q_positions=positions, kv_positions=positions,
                return_mass=True, mass_group=attn.MASS_GROUP)
        B, T, _ = x.shape
        x = x + L.linear(p["attn"]["wo"], o.reshape(B, T, -1))
        lc = kvcache.compress_prompt(spec, k, v, mass, key=key, dtype=cfg.dtype,
                                     logical_budget=logical_budget)
        x = _cross_attend(p, x, memory_kv, cfg)
        x, moe_st = _ffn_serve(p, x, cfg)
        return x, moe_st, lc
    else:
        o, st = ssm_lib.mamba2_forward(p["ssm"], h, cfg)
        x = x + o
        x = _cross_attend(p, x, memory_kv, cfg)
        x, moe_st = _ffn_serve(p, x, cfg)
        return x, moe_st, st


# ---------------------------------------------------------------------------
# Chunked prefill: one prompt segment against the admission scratch
# ---------------------------------------------------------------------------


def block_prefill_chunk(p: dict, x: Array, cfg, spec: CacheSpec,
                        k_scr: Array, v_scr: Array, mass_scr: Array,
                        positions: Array):
    """One attention layer's step of a chunked prefill (attn blocks only —
    `nn.model.prefill_chunk` gates SSM archs).

    x: [1, C, d_model] — the current segment's hidden states; positions:
    [1, C] absolute prompt positions (contiguous, MASS_GROUP-aligned
    start). k_scr/v_scr: [1, T, Hkv, D] full-precision prompt K/V scratch
    (rows beyond this segment still zero); mass_scr: [1, T] running
    attention mass. The segment's K/V are written into the scratch first,
    then its queries attend to the whole scratch under the ordinary
    causal mask — full attention to the prefix, causal within the
    segment. Because every op outside attention is query-row-independent
    and the attention keys span the same [T] axis as the monolithic pass,
    activations (and therefore the scratch handed to
    `cache.compress_prompt` at finalize) are bit-identical to a
    monolithic `block_prefill` over the whole prompt.

    A latent (MLA) scratch holds the prompt's latent rows in k_scr [1, T,
    latent_store_dim] (v_scr is zero-wide): the segment's rows are
    written, and its queries attend over the whole scratch expanded to
    per-head K/V (re-expanded every segment; only rows go into the
    store).

    Returns (x, k_scr, v_scr, mass_scr, MoE stats or None) updated."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    c0 = positions[0, 0]
    if cfg.is_mla:
        pa = p["attn"]
        rows = mla.latent_rows(pa, h, cfg, positions)
        k_scr = jax.lax.dynamic_update_slice_in_dim(
            k_scr, rows.astype(k_scr.dtype), c0, axis=1)
        q_nope, q_pe = mla.queries(pa, h, cfg, positions)
        o = mla.attend_expanded(pa, q_nope, q_pe, k_scr, cfg, positions)
        x = x + L.linear(pa["wo"], o)
        x, st = _ffn_serve(p, x, cfg)
        return x, k_scr, v_scr, mass_scr, st
    q, k, v = attn.qkv(p["attn"], h, cfg, positions)
    k_scr = jax.lax.dynamic_update_slice_in_dim(k_scr, k.astype(k_scr.dtype),
                                                c0, axis=1)
    v_scr = jax.lax.dynamic_update_slice_in_dim(v_scr, v.astype(v_scr.dtype),
                                                c0, axis=1)
    if _use_flash_prefill_chunk(cfg, spec):
        # same dispatch rule as the monolithic path: policies that never
        # read the mass statistic take the flash kernel (and record zero
        # mass there too, so the two engines stay comparable)
        from repro.kernels.flash_prefill import ops as fp_ops
        o = fp_ops.flash_attention_chunk(q, k_scr, v_scr, q_offset=c0,
                                         window=cfg.sliding_window)
    else:
        o, mass_scr = attn.gqa_attention(
            q, k_scr, v_scr, causal=True, window=cfg.sliding_window,
            q_positions=positions, return_mass=True,
            mass_group=attn.MASS_GROUP, mass_init=mass_scr)
    B, C, _ = x.shape
    x = x + L.linear(p["attn"]["wo"], o.reshape(B, C, -1))
    x, st = _ffn_serve(p, x, cfg)
    return x, k_scr, v_scr, mass_scr, st


def _use_flash_prefill_chunk(cfg, spec: CacheSpec) -> bool:
    """Chunk twin of `_use_flash_prefill`: the chunk variant of the flash
    kernel takes the query offset explicitly, so standard-arange
    positions are implied rather than required."""
    return (attn.resolve_use_kernels(getattr(cfg, "use_kernels", None))
            and not spec.track_scores())


# ---------------------------------------------------------------------------
# Speculative verify: one multi-token segment against the cache
# ---------------------------------------------------------------------------


def block_verify(p: dict, x: Array, cfg, spec: CacheSpec, lc,
                 valid_len: Array, *, key: Optional[Array] = None):
    """One attention layer's step of a speculative verify (attn blocks
    only — `nn.model.verify_step` gates other archs).

    x: [B, L, d_model] — the speculated segment (last committed token +
    drafts, row b ragged at `valid_len[b]`, padded rows inert). The
    segment's K/V are appended first (`cache.append_segment`, bit-equal
    to L sequential `append_token`s per row), then every query row
    attends over the cache in one rectangular pass
    (`attn.verify_attention`) — bit-identical per row to the L
    sequential `block_decode` attends it replaces. Score accumulation is
    *deferred*: the per-row masses are returned so `verify_step` can
    apply exactly the accepted rows' masses once acceptance is known.

    Returns (x, appended cache piece, row_mass [B, L, S+W])."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    lc_pos0 = lc.pos
    B, Lseg, _ = x.shape
    positions = lc_pos0[:, None] + jnp.arange(Lseg)[None]     # [B, L]
    q, k_new, v_new = attn.qkv(p["attn"], h, cfg, positions)
    lc = kvcache.append_segment(lc, spec, k_new, v_new, key=key,
                                valid_len=valid_len)
    o, row_mass = attn.verify_attention(
        q, lc, spec, q_pos=positions, window=cfg.sliding_window,
        dtype=cfg.dtype, use_kernels=getattr(cfg, "use_kernels", None))
    x = x + L.linear(p["attn"]["wo"], o.reshape(B, Lseg, -1))
    x, _ = _ffn_serve(p, x, cfg)
    return x, lc, row_mass


# ---------------------------------------------------------------------------
# Decode: one token against the cache
# ---------------------------------------------------------------------------


def block_decode(p: dict, x: Array, cfg, kind: str, spec: CacheSpec,
                 cache_piece, *, key: Optional[Array] = None, memory_kv=None,
                 append_mask: Optional[Array] = None,
                 layer: Optional[Array] = None,
                 live: Optional[Array] = None):
    """x: [B, 1, d_model]. Returns (x, new cache piece, this call's MoE
    stats or None for a dense layer).

    append_mask: optional [B] bool — rows where it is False leave the
    cache untouched (their attention output is still computed, and
    discarded by the caller). Used by the speculative drafter, whose
    per-slot draft depths are ragged.

    layer: optional int32 scalar — the piece's k/v are the layer-stacked
    dense stores, written and attended at layer `layer` in place
    (`cache.append_token_dense`).

    live: optional [B] bool — the slots serving a request, the only rows
    the MoE counters count (`moe.moe_apply_dropfree`).

    A latent (MLA) store appends the token's latent row and attends in
    the absorbed form (`nn/mla.py`, `kernels/decode_mla`)."""
    h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
    if kind == "attn" and cfg.is_mla:
        lc: LayerKV = cache_piece
        pa = p["attn"]
        pos = lc.pos[:, None]                                  # [B, 1]
        rows = mla.latent_rows(pa, h, cfg, pos)[:, 0]          # [B, D]
        lc = kvcache.append_token(lc, spec, rows, rows[:, :0], key=key,
                                  mask=append_mask, layer=layer)
        q_nope, q_pe = mla.queries(pa, h, cfg, pos)
        q = mla.absorb_query(pa, q_nope, q_pe, cfg)[:, 0]       # [B, H, D]
        o_lat = mla.decode_attention(
            q, lc, cfg, layer=layer,
            use_kernels=getattr(cfg, "use_kernels", None))
        x = x + L.linear(pa["wo"], mla.absorbed_output(pa, o_lat[:, None],
                                                       cfg))
        new_piece = lc
    elif kind == "attn":
        lc: LayerKV = cache_piece
        pos = lc.pos[:, None]                                  # [B, 1]
        q, k_new, v_new = attn.qkv(p["attn"], h, cfg, pos)
        # append-first: the new token attends to itself through the cache
        lc = kvcache.append_token(lc, spec, k_new[:, 0], v_new[:, 0], key=key,
                                  mask=append_mask, layer=layer)
        o, mass = attn.decode_attention(
            q, lc, spec, window=cfg.sliding_window, dtype=cfg.dtype,
            q_pos=pos[:, 0], use_kernels=getattr(cfg, "use_kernels", None),
            layer=layer)
        lc = kvcache.accumulate_scores(lc, spec, mass, key=key,
                                       gate=append_mask)
        B = x.shape[0]
        x = x + L.linear(p["attn"]["wo"], o.reshape(B, 1, -1))
        new_piece = lc
    else:
        st: SSMState = cache_piece
        o, st = ssm_lib.mamba2_decode_step(p["ssm"], h, st, cfg)
        x = x + o
        new_piece = st
    x = _cross_attend(p, x, memory_kv, cfg)
    x, moe_st = _ffn_serve(p, x, cfg,
                           None if live is None else live[:, None])
    return x, new_piece, moe_st
