"""The LM: init / train_forward / prefill / decode_step over any assigned
architecture.

Layers are organized into **superblocks** of size
``lcm(attn_layer_period, moe.layer_period)`` (1 for uniform models, 8 for
Jamba) and the model scans over superblocks with stacked params — one HLO
body regardless of depth, which keeps 512-device dry-run compiles fast.
Within a superblock, sublayer kinds (attn|ssm × dense|moe) are unrolled
statically.

Leading dense layers (DeepSeek's `first_k_dense_replace`) are held apart
as `params["lead"]`, stacked the same way, and every layer loop scans
them first (`_scan_layers`); caches stay stacked over all layers.

The decode cache is a `ModelCache`: compressed `LayerKV` stacks for
attention layers (the survey's subject) — for latent attention (MLA)
one latent row per token and layer (`cache.is_latent`) — `SSMState`
stacks for Mamba layers, static cross-attention memory for enc-dec,
and the drop-free MoE routing counters the serving loop publishes.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import cache as kvcache
from repro.core.cache import CacheSpec, LayerKV
from repro.kernels.blocking import reads_in_place
from repro.nn import blocks as B
from repro.nn import layers as L
from repro.nn import ssm as ssm_lib
from repro.nn.moe import MoEStats, add_stats

Array = jax.Array


class ModelCache(NamedTuple):
    attn: Any        # LayerKV, leaves [n_sb, nA, ...] (None if no attn layers)
    ssm: Any         # SSMState, leaves [n_sb, nS, ...] (None if none)
    cross_k: Any     # [L, B, Ts, Hkv, D] enc-dec only, else None
    cross_v: Any
    cross_bias: Any  # [B, Ts]
    moe: Any = None  # MoEStats summed over the MoE layer calls of the
                     # steps and prompts this cache went through (MoE only)


class TrainAux(NamedTuple):
    lb_loss: Array
    z_loss: Array


# ---------------------------------------------------------------------------
# Superblock layout
# ---------------------------------------------------------------------------


def sb_layout(cfg):
    """Returns (sb, n_sb, kinds) where kinds[i] = (mixer_kind, ffn_kind)
    of the superblocks after the leading dense layers; n_sb counts the
    leading layers too (each is a superblock of one)."""
    p1 = cfg.attn_layer_period if cfg.attn_layer_period > 0 else 1
    p2 = cfg.moe.layer_period if cfg.is_moe else 1
    sb = math.lcm(p1, p2)
    lead = cfg.lead_layers
    assert cfg.num_layers % sb == 0, (cfg.num_layers, sb)
    assert not lead or sb == 1, "leading dense layers need uniform layers"
    kinds = [(cfg.layer_kind(lead + i), cfg.ffn_kind(lead + i))
             for i in range(sb)]
    return sb, cfg.num_layers // sb, kinds


def _scan_layers(cfg, params, body, carry, xs: tuple):
    """`lax.scan` of `body(carry, (p_sb, *xs_i))` over the layer stack:
    the leading dense layers (`params["lead"]`) first, then
    `params["blocks"]`. Leaves of `xs` and of the stacked outputs span
    all n_sb superblocks."""
    n = cfg.lead_layers
    if not n:
        return jax.lax.scan(body, carry, (params["blocks"], *xs))
    cut = lambda a, b: jax.tree.map(lambda t: t[a:b], xs)
    carry, ys0 = jax.lax.scan(body, carry, (params["lead"], *cut(0, n)))
    carry, ys1 = jax.lax.scan(body, carry,
                              (params["blocks"], *cut(n, None)))
    return carry, jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                               ys0, ys1)



def attn_positions(cfg):
    sb, n_sb, kinds = sb_layout(cfg)
    return [i for i, (k, _) in enumerate(kinds) if k == "attn"]


def ssm_positions(cfg):
    sb, n_sb, kinds = sb_layout(cfg)
    return [i for i, (k, _) in enumerate(kinds) if k == "ssm"]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key: Array, cfg) -> dict:
    sb, n_sb, kinds = sb_layout(cfg)
    keys = jax.random.split(key, 6)
    params: dict = {
        "embed": L.embedding_init(keys[0], cfg.vocab_size, cfg.d_model,
                                  cfg.dtype),
        "final_norm": L.rmsnorm_init(cfg.d_model, cfg.dtype),
    }
    cross = cfg.is_encoder_decoder

    def init_sb(k):
        ks = jax.random.split(k, sb)
        return {
            f"sub{i}": B.block_init(ks[i], cfg, kinds[i][0], kinds[i][1],
                                    cross=cross)
            for i in range(sb)
        }

    lead = cfg.lead_layers
    params["blocks"] = jax.vmap(init_sb)(jax.random.split(keys[1],
                                                          n_sb - lead))
    if lead:
        params["lead"] = jax.vmap(
            lambda k: {"sub0": B.block_init(k, cfg, "attn", "dense")})(
                jax.random.split(keys[4], lead))
    if not cfg.tie_embeddings:
        params["head"] = L.linear_init(keys[2], cfg.d_model, cfg.vocab_size,
                                       bias=False, dtype=cfg.dtype)
    if cfg.is_encoder_decoder:
        def init_enc(k):
            return B.block_init(k, cfg, "attn", "dense")
        params["enc_blocks"] = jax.vmap(init_enc)(
            jax.random.split(keys[3], cfg.num_encoder_layers))
        params["enc_norm"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
    return params


def _logits(params, cfg, x: Array) -> Array:
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], x)
    return L.linear(params["head"], x).astype(jnp.float32)


def _maybe_remat(cfg, fn):
    return jax.checkpoint(fn) if cfg.remat == "block" else fn


# ---------------------------------------------------------------------------
# Encoder (enc-dec archs; bidirectional over stubbed frame embeddings)
# ---------------------------------------------------------------------------


def encode(params, cfg, src_embeds: Array) -> Array:
    """src_embeds: [B, Ts, d_model] from the stubbed modality frontend."""
    def body(x, p):
        x, _ = B.block_train(p, x, cfg, "attn", causal=False)
        return x, None
    x, _ = jax.lax.scan(_maybe_remat(cfg, body), src_embeds,
                        params["enc_blocks"])
    return L.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _cross_memory(params, cfg, memory: Array):
    """Precompute per-decoder-layer cross K/V: [L, B, Ts, Hkv, D]."""
    def per_layer(p):
        return B.cross_kv(p, memory, cfg)
    sb, n_sb, kinds = sb_layout(cfg)
    assert sb == 1, "enc-dec assumes uniform decoder layers"
    ks, vs = jax.vmap(per_layer)(
        jax.tree.map(lambda a: a, params["blocks"]["sub0"]))
    bias = jnp.zeros((memory.shape[0], memory.shape[1]), jnp.float32)
    return ks, vs, bias


# ---------------------------------------------------------------------------
# Train forward
# ---------------------------------------------------------------------------


def train_forward(params, cfg, batch: dict):
    """batch: {"tokens": [B, S]} (+ "src_embeds" [B, Ts, d] for enc-dec).
    Returns (logits [B, S, V] f32, TrainAux)."""
    tokens = batch["tokens"]
    x = L.embed(params["embed"], tokens)
    memory = None
    if cfg.is_encoder_decoder:
        memory = encode(params, cfg, batch["src_embeds"].astype(cfg.dtype))
    sb, n_sb, kinds = sb_layout(cfg)

    def body(carry, xs):
        x, lb, zl = carry
        p_sb, = xs
        for i in range(sb):
            mk = None
            if cfg.is_encoder_decoder:
                k_, v_ = B.cross_kv(p_sb[f"sub{i}"], memory, cfg)
                mk = (k_, v_, None)
            # positions=None: standard arange (built inside qkv/attention)
            # — the contract that lets blocks dispatch the flash kernel
            x, aux = B.block_train(p_sb[f"sub{i}"], x, cfg, kinds[i][0],
                                   positions=None, memory_kv=mk)
            lb, zl = lb + aux.lb_loss, zl + aux.z_loss
        return (x, lb, zl), None

    (x, lb, zl), _ = _scan_layers(cfg, params, _maybe_remat(cfg, body),
                                  (x, jnp.zeros(()), jnp.zeros(())), ())
    return _logits(params, cfg, x), TrainAux(lb, zl)


# ---------------------------------------------------------------------------
# Prefill: run the prompt, build the compressed cache
# ---------------------------------------------------------------------------


def prefill(params, cfg, batch: dict, spec: CacheSpec, *,
            layer_budgets: Optional[Array] = None,
            key: Optional[Array] = None):
    """Returns (last-token logits [B, V], ModelCache)."""
    tokens = batch["tokens"]
    x = L.embed(params["embed"], tokens)
    T = tokens.shape[1]
    sb, n_sb, kinds = sb_layout(cfg)
    aps, sps = attn_positions(cfg), ssm_positions(cfg)

    memory = None
    cross = (None, None, None)
    if cfg.is_encoder_decoder:
        memory = encode(params, cfg, batch["src_embeds"].astype(cfg.dtype))
        cross = _cross_memory(params, cfg, memory)

    if key is None:
        key = jax.random.key(0)
    keys = jax.random.split(key, n_sb * max(len(aps), 1)).reshape(
        n_sb, max(len(aps), 1))
    if layer_budgets is None:
        S_phys = spec.main_store_len(T)
        layer_budgets = jnp.full((n_sb, max(len(aps), 1)), S_phys, jnp.int32)
    else:
        layer_budgets = jnp.asarray(layer_budgets, jnp.int32).reshape(
            n_sb, max(len(aps), 1))

    def body(carry, xs):
        x, stats = carry
        p_sb, ks, buds = xs
        attn_pieces, ssm_pieces = [], []
        for i in range(sb):
            mkv = None
            if cfg.is_encoder_decoder:
                k_, v_ = B.cross_kv(p_sb[f"sub{i}"], memory, cfg)
                mkv = (k_, v_, None)
            if kinds[i][0] == "attn":
                j = aps.index(i)
                # positions=None: standard arange (flash-kernel eligible)
                x, st, piece = B.block_prefill(
                    p_sb[f"sub{i}"], x, cfg, "attn", spec,
                    positions=None, logical_budget=buds[j],
                    key=ks[j], memory_kv=mkv)
                attn_pieces.append(piece)
            else:
                x, st, piece = B.block_prefill(
                    p_sb[f"sub{i}"], x, cfg, "ssm", spec,
                    positions=None, memory_kv=mkv)
                ssm_pieces.append(piece)
            stats = add_stats(stats, st)
        a = (jax.tree.map(lambda *xs: jnp.stack(xs), *attn_pieces)
             if attn_pieces else None)
        s = (jax.tree.map(lambda *xs: jnp.stack(xs), *ssm_pieces)
             if ssm_pieces else None)
        return (x, stats), (a, s)

    stats = MoEStats.zero() if cfg.is_moe else None
    (x, stats), (attn_c, ssm_c) = _scan_layers(
        cfg, params, body, (x, stats), (keys, layer_budgets))
    logits = _logits(params, cfg, x[:, -1:])[:, 0]
    return logits, ModelCache(attn_c, ssm_c, *cross, moe=stats)


# ---------------------------------------------------------------------------
# Chunked prefill: stream the prompt in segments, compress at the end
# ---------------------------------------------------------------------------
#
# A monolithic prefill of a long prompt is one big compiled call — during
# a continuous-batching admission it stalls every resident slot's decode
# for its whole duration. Chunked prefill splits the prompt into
# MASS_GROUP-aligned segments the engine interleaves between decode
# steps. Each segment runs against a full-precision per-admission
# *scratch* (`PrefillState`): its K/V rows are written into the scratch,
# its queries attend causally over the whole scratch (full attention to
# the prefix — the already-streamed rows — causal within the segment),
# and attention mass accumulates via the canonical grouped fold
# (`nn.attention.MASS_GROUP`). `prefill_finalize` then runs the same
# per-layer `compress_prompt` the monolithic path runs, on bit-identical
# inputs — so chunked and monolithic admissions produce bit-identical
# caches, logits, and greedy token streams (the serving contract;
# tests/test_chunked_prefill.py).
#
# Attention-only decoder archs: SSM state couples tokens across segment
# boundaries, so those archs are gated (ValueError). MoE layers route
# drop-free on this path (`moe.moe_apply_dropfree`): a token's output
# does not depend on the segment it shares.


class PrefillState(NamedTuple):
    """Per-admission scratch: exact prompt K/V + running attention mass.
    Leaves are layer-stacked like `ModelCache.attn` ([n_sb, nA, ...]).
    A latent (MLA) scratch holds the latent rows in `k` ([.., 1, T,
    latent_store_dim]) and a zero-wide `v`, as the latent store does."""

    k: Any      # [n_sb, nA, 1, T, Hkv, D] model dtype
    v: Any      # [n_sb, nA, 1, T, Hkv, D]
    mass: Any   # [n_sb, nA, 1, T] f32
    moe: Any = None  # MoEStats of the prompt's segments (MoE only)


def _check_chunkable(cfg) -> None:
    """Chunked prefill runs decoder-only attention stacks (dense or
    drop-free MoE FFNs); it refuses SSM layers and encoder-decoders."""
    if ssm_positions(cfg):
        raise ValueError("chunked prefill is attention-only: SSM state "
                         "carries across segments (sequential scan), so "
                         "SSM and hybrid models are refused")
    if cfg.is_encoder_decoder:
        raise ValueError("chunked prefill is decoder-only: "
                         "encoder-decoder models are refused")


def kv_store_dims(cfg) -> tuple[int, int, int]:
    """(heads, K width, V width) of one cached row: an MLA model's latent
    row (no head axis, `latent_store_dim` wide, no V of its own;
    `init_layer_kv`), else (num_kv_heads, head_dim, head_dim)."""
    if cfg.is_mla:
        return 0, cfg.latent_store_dim, 0
    return cfg.num_kv_heads, cfg.head_dim, cfg.head_dim


def init_prefill_state(cfg, prompt_len: int) -> PrefillState:
    _check_chunkable(cfg)
    sb, n_sb, _ = sb_layout(cfg)
    nA = len(attn_positions(cfg))
    H, D, Dv = kv_store_dims(cfg)
    hd = (H,) if H else ()
    return PrefillState(
        k=jnp.zeros((n_sb, nA, 1, prompt_len, *hd, D), cfg.dtype),
        v=jnp.zeros((n_sb, nA, 1, prompt_len, *hd, Dv), cfg.dtype),
        mass=jnp.zeros((n_sb, nA, 1, prompt_len), jnp.float32),
        moe=MoEStats.zero() if cfg.is_moe else None,
    )


def prefill_chunk(params, cfg, st: PrefillState, tokens: Array, c0,
                  spec: CacheSpec):
    """Run one prompt segment. tokens: [1, C] (C MASS_GROUP-aligned
    except a final ragged segment); c0: scalar int32 absolute start
    (traced — one compile per segment *length*, not per offset).
    Returns (logits [1, V] of the segment's last token, new state)."""
    x = L.embed(params["embed"], tokens)
    C = tokens.shape[1]
    positions = c0 + jnp.arange(C)[None]
    sb, n_sb, kinds = sb_layout(cfg)
    aps = attn_positions(cfg)

    assert all(k == "attn" for k, _ in kinds), "gated by _check_chunkable"

    def body(carry, xs):
        x, stats = carry
        p_sb, k_sl, v_sl, m_sl = xs
        ks, vs, ms = [], [], []
        for i in range(sb):
            j = aps.index(i)
            x, k_j, v_j, m_j, moe_st = B.block_prefill_chunk(
                p_sb[f"sub{i}"], x, cfg, spec,
                k_sl[j], v_sl[j], m_sl[j], positions)
            ks.append(k_j); vs.append(v_j); ms.append(m_j)
            stats = add_stats(stats, moe_st)
        return (x, stats), (jnp.stack(ks), jnp.stack(vs), jnp.stack(ms))

    (x, stats), (k_n, v_n, m_n) = _scan_layers(
        cfg, params, body, (x, st.moe), (st.k, st.v, st.mass))
    logits = _logits(params, cfg, x[:, -1:])[:, 0]
    return logits, PrefillState(k_n, v_n, m_n, stats)


def prefill_finalize(cfg, st: PrefillState, spec: CacheSpec, *,
                     layer_budgets: Optional[Array] = None,
                     key: Optional[Array] = None) -> ModelCache:
    """Compress the completed scratch into a batch-1 `ModelCache` — the
    same per-layer `compress_prompt` calls (same key/budget splitting) as
    monolithic `prefill`, so the result is insert-compatible with
    `Engine._insert` and bit-identical to the monolithic cache."""
    sb, n_sb, kinds = sb_layout(cfg)
    aps = attn_positions(cfg)
    nA = max(len(aps), 1)
    if key is None:
        key = jax.random.key(0)
    keys = jax.random.split(key, n_sb * nA).reshape(n_sb, nA)
    T = st.mass.shape[-1]
    if layer_budgets is None:
        S_phys = spec.main_store_len(T)
        layer_budgets = jnp.full((n_sb, nA), S_phys, jnp.int32)
    else:
        layer_budgets = jnp.asarray(layer_budgets, jnp.int32).reshape(
            n_sb, nA)

    def body(carry, xs):
        k_sl, v_sl, m_sl, ks, buds = xs
        pieces = []
        for i in range(sb):
            j = aps.index(i)
            pieces.append(kvcache.compress_prompt(
                spec, k_sl[j], v_sl[j], m_sl[j], key=ks[j],
                dtype=cfg.dtype, logical_budget=buds[j]))
        return carry, jax.tree.map(lambda *xs: jnp.stack(xs), *pieces)

    _, attn_c = jax.lax.scan(
        body, 0, (st.k, st.v, st.mass, keys, layer_budgets))
    return ModelCache(attn_c, None, None, None, None, moe=st.moe)


def prefill_finalize_meta(cfg, st: PrefillState, spec: CacheSpec, *,
                          layer_budgets: Optional[Array] = None
                          ) -> ModelCache:
    """Metadata-only finalize for the paged prefill-direct path: when the
    policy keeps every prompt row verbatim (no quantization, no window,
    budget covers the prompt — `compress_prompt`'s no-selection branch)
    the engine streams each chunk's K/V rows straight into the pool
    (`paging.write_prefill_rows`), so finalize only needs the dense
    *metadata* that branch would produce. K/V leaves are zero-width: the
    insert runs with ``pool_write=False`` and never reads them."""
    sb, n_sb, kinds = sb_layout(cfg)
    aps = attn_positions(cfg)
    nA = max(len(aps), 1)
    T = st.mass.shape[-1]
    S = spec.main_store_len(T)
    if not (S >= T and not spec.quantized and spec.window == 0):
        raise ValueError("prefill-direct needs the verbatim prompt branch "
                         "(budget >= prompt, fp, no window)")
    if layer_budgets is None:
        layer_budgets = jnp.full((n_sb, nA), S, jnp.int32)
    else:
        layer_budgets = jnp.asarray(layer_budgets, jnp.int32).reshape(
            n_sb, nA)
    H, D = cfg.num_kv_heads, cfg.head_dim
    pad = S - T
    bshape = (n_sb, nA, 1)                    # layer-stacked, batch 1
    pos_rows = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                (*bshape, T))
    pad_last = ((0, 0),) * 3 + ((0, pad),)
    attn_c = LayerKV(
        k=jnp.zeros((*bshape, 0, H, D), cfg.dtype),
        v=jnp.zeros((*bshape, 0, H, D), cfg.dtype),
        k_scale=jnp.zeros((*bshape, 0, H, D), jnp.float32),
        k_zero=jnp.zeros((*bshape, 0, H, D), jnp.float32),
        v_scale=jnp.zeros((*bshape, 0, H), jnp.float32),
        v_zero=jnp.zeros((*bshape, 0, H), jnp.float32),
        rk=jnp.zeros((*bshape, 0, H, D), cfg.dtype),
        rv=jnp.zeros((*bshape, 0, H, D), cfg.dtype),
        r_scores=jnp.zeros((*bshape, 0), jnp.float32),
        scores=jnp.pad(st.mass.astype(jnp.float32), pad_last),
        slot_pos=jnp.pad(pos_rows, pad_last, constant_values=-1),
        length=jnp.full(bshape, T, jnp.int32),
        rlen=jnp.zeros(bshape, jnp.int32),
        pos=jnp.full(bshape, T, jnp.int32),
        budget=layer_budgets,
    )
    return ModelCache(attn_c, None, None, None, None)


def prefill_from_kv(cfg, spec: CacheSpec, ks: Array, vs: Array, *,
                    layer_budgets: Optional[Array] = None,
                    key: Optional[Array] = None) -> ModelCache:
    """Build an insert-compatible prefill cache from externally computed
    per-layer K/V ``[L, B, S, H, D]`` (CacheBlend's blended prompt KV).
    Attention mass is zeroed — only legal for policies whose selection
    ignores it (the engine gates near-hits to policy "none"). Uniform
    decoder archs only (sb == 1), like `cacheblend`."""
    sb, n_sb, kinds = sb_layout(cfg)
    if sb != 1 or len(attn_positions(cfg)) != 1:
        raise ValueError("prefill_from_kv assumes uniform attention layers")
    st = PrefillState(
        k=ks[:, None].astype(cfg.dtype), v=vs[:, None].astype(cfg.dtype),
        mass=jnp.zeros((n_sb, 1, *ks.shape[1:3]), jnp.float32))
    return prefill_finalize(cfg, st, spec, layer_budgets=layer_budgets,
                            key=key)


# ---------------------------------------------------------------------------
# Decode: one token
# ---------------------------------------------------------------------------


def decode_in_place(cache: ModelCache, spec: CacheSpec) -> bool:
    """Whether `decode_step` updates this cache's attention store in
    place: a dense (bits 16), non-paged store whose layout lets the
    kernel read it where it lies (`blocking.reads_in_place`: whole
    128-lane heads, more than one KV head). Its K/V layer stacks are
    then carried through the layer loop, each layer's new rows are
    scattered into the stack, and the decode kernel reads the layer where
    it lies — the stack is never sliced out, transposed or rebuilt.
    A latent (MLA) store always is: its decode kernel reads whole rows
    (`kernels/decode_mla`). Other stores pass through the loop layer by
    layer."""
    return (isinstance(cache.attn, LayerKV) and not spec.quantized
            and (kvcache.is_latent(cache.attn)
                 or reads_in_place(*cache.attn.k.shape[-2:])))


def decode_step(params, cfg, cache: ModelCache, token: Array,
                spec: CacheSpec, *, key: Optional[Array] = None,
                append_mask: Optional[Array] = None,
                live: Optional[Array] = None):
    """token: [B, 1] int32. Returns (logits [B, V] f32, new ModelCache).

    append_mask: optional [B] bool — rows where it is False leave the
    cache untouched (ragged speculative drafting; attention-only archs,
    SSM state cannot be row-gated against its own step).

    live: optional [B] bool — the slots serving a request; the MoE
    counters (`ModelCache.moe`) count only their rows.

    A dense store is updated in place (`decode_in_place`): with the
    cache donated, the step's only writes to it are one row per slot and
    layer."""
    x = L.embed(params["embed"], token)
    sb, n_sb, kinds = sb_layout(cfg)
    aps, sps = attn_positions(cfg), ssm_positions(cfg)
    if append_mask is not None and ssm_positions(cfg):
        raise ValueError("append_mask is attention-only (SSM state "
                         "advances unconditionally)")
    if key is None:
        key = jax.random.key(0)
    keys = jax.random.split(key, n_sb * max(len(aps), 1)).reshape(
        n_sb, max(len(aps), 1))

    has_cross = cache.cross_k is not None
    in_place = decode_in_place(cache, spec)
    attn_xs, kv = cache.attn, ()
    if in_place:
        # [n_sb, nA, B, S, H, D] -> [n_sb * nA, B, S, H, D]: a bitcast
        kv_shapes = (cache.attn.k.shape, cache.attn.v.shape)
        kv = tuple(t.reshape(t.shape[0] * t.shape[1], *t.shape[2:])
                   for t in (cache.attn.k, cache.attn.v))
        attn_xs = cache.attn._replace(k=None, v=None)

    def body(carry, xs):
        x, kv, stats = carry
        p_sb, a_sl, s_sl, ks, ck, cv, i_sb = xs
        attn_pieces, ssm_pieces = [], []
        for i in range(sb):
            mkv = None
            if has_cross:
                mkv = (ck, cv, cache.cross_bias)
            if kinds[i][0] == "attn":
                j = aps.index(i)
                piece = jax.tree.map(lambda t: t[j], a_sl)
                layer = None
                if in_place:
                    layer = i_sb * len(aps) + j
                    piece = piece._replace(k=kv[0], v=kv[1])
                x, piece, st = B.block_decode(
                    p_sb[f"sub{i}"], x, cfg, "attn", spec, piece,
                    key=ks[j], memory_kv=mkv, append_mask=append_mask,
                    layer=layer, live=live)
                if in_place:
                    kv = (piece.k, piece.v)
                    piece = piece._replace(k=None, v=None)
                attn_pieces.append(piece)
            else:
                j = sps.index(i)
                piece = jax.tree.map(lambda t: t[j], s_sl)
                x, piece, st = B.block_decode(
                    p_sb[f"sub{i}"], x, cfg, "ssm", spec, piece,
                    memory_kv=mkv, live=live)
                ssm_pieces.append(piece)
            stats = add_stats(stats, st)
        a = (jax.tree.map(lambda *xs: jnp.stack(xs), *attn_pieces)
             if attn_pieces else None)
        s = (jax.tree.map(lambda *xs: jnp.stack(xs), *ssm_pieces)
             if ssm_pieces else None)
        return (x, kv, stats), (a, s)

    cross_k = cache.cross_k if has_cross else jnp.zeros((n_sb, 0))
    cross_v = cache.cross_v if has_cross else jnp.zeros((n_sb, 0))
    (x, kv, stats), (attn_c, ssm_c) = _scan_layers(
        cfg, params, body, (x, kv, cache.moe),
        (attn_xs, cache.ssm, keys, cross_k, cross_v, jnp.arange(n_sb)))
    if in_place:
        attn_c = attn_c._replace(k=kv[0].reshape(kv_shapes[0]),
                                 v=kv[1].reshape(kv_shapes[1]))
    logits = _logits(params, cfg, x)[:, 0]
    return logits, cache._replace(attn=attn_c, ssm=ssm_c, moe=stats)


# ---------------------------------------------------------------------------
# Speculative verify: score a drafted segment in one forward, commit the
# accepted prefix, roll the rest back
# ---------------------------------------------------------------------------
#
# One decode step per token is weight-bandwidth-bound: every step moves
# all parameters for one token per slot. Self-speculative decoding
# drafts gamma tokens against a *cheap cache view* of the same weights
# (serving/speculative.py), then this function scores the whole segment
# — last committed token + drafts — in ONE forward over the real
# budgeted cache: the segment's K/V are appended (`append_segment`,
# bit-equal to sequential appends), every row attends rectangularly
# (`verify_attention`, bit-identical per row to sequential decode), and
# greedy acceptance reduces rejection sampling to match-and-truncate.
# Rejected rows are un-appended (`cache.truncate_rows`) and only the
# accepted queries' attention masses are accumulated — in sequential
# order with exact-zero padding, so the score state (H2O et al.) is
# bit-identical to the sequential decode it replaces.
#
# Dense attention-only decoder archs (the chunked-prefill gate, and no
# routed experts or latent store yet): SSM state cannot be rolled back
# row-wise.


def _check_speculable(cfg) -> None:
    try:
        _check_chunkable(cfg)
    except ValueError as e:
        raise ValueError(f"speculative decoding: {e}") from None
    if cfg.is_moe:
        raise ValueError("speculative decoding with routed experts is not "
                         "supported: the verify step and the drafter "
                         "have no MoE path")
    if cfg.is_mla:
        raise ValueError("speculative decoding over a latent (MLA) store "
                         "is not supported: the verify step has no "
                         "latent path")


def verify_step(params, cfg, cache: ModelCache, tokens: Array,
                valid_len: Array, spec: CacheSpec, *,
                key: Optional[Array] = None):
    """tokens: [B, L] int32 — per row: [last committed token, draft_1 ..
    draft_{gamma_b}, padding]; valid_len: [B] int32 segment lengths
    (1 + gamma_b; 0 for slots that must not step at all).

    Returns (y [B, L] int32, accepted [B] int32, new ModelCache):
    `y[b, t]` is the greedy target token after processing row b's tokens
    0..t; `accepted[b]` counts the leading drafts that matched (so
    tokens `y[b, 0..accepted[b]]` are committed — accepted drafts plus
    the bonus/correction token). The returned cache has exactly the
    committed rows appended: acceptance, score accumulation, and ragged
    rollback all happen inside this one step."""
    _check_speculable(cfg)
    x = L.embed(params["embed"], tokens)
    Lseg = tokens.shape[1]
    sb, n_sb, kinds = sb_layout(cfg)
    aps = attn_positions(cfg)
    assert all(k == "attn" for k, _ in kinds), "gated by _check_speculable"
    if key is None:
        key = jax.random.key(0)
    nA = max(len(aps), 1)
    keys = jax.random.split(key, n_sb * nA * 2).reshape(n_sb, nA, 2)

    def body(x, xs):
        p_sb, a_sl, ks = xs
        pieces, masses = [], []
        for i in range(sb):
            j = aps.index(i)
            piece = jax.tree.map(lambda t: t[j], a_sl)
            x, piece, rm = B.block_verify(p_sb[f"sub{i}"], x, cfg, spec,
                                          piece, valid_len, key=ks[j, 0])
            pieces.append(piece)
            masses.append(rm)
        a = jax.tree.map(lambda *xs: jnp.stack(xs), *pieces)
        return x, (a, jnp.stack(masses))

    x, (attn_c, masses) = jax.lax.scan(
        body, x, (params["blocks"], cache.attn, keys))
    logits = _logits(params, cfg, x)                       # [B, L, V]
    y = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # longest accepted draft prefix: draft_i (= tokens[:, i]) must equal
    # the target's y[:, i-1] for every i up to the cut
    if Lseg > 1:
        match = (tokens[:, 1:] == y[:, :-1])
        valid_draft = jnp.arange(Lseg - 1)[None] < (valid_len[:, None] - 1)
        accepted = jnp.cumprod((match & valid_draft).astype(jnp.int32),
                               axis=1).sum(axis=1)
    else:
        accepted = jnp.zeros(tokens.shape[0], jnp.int32)
    n_drop = jnp.maximum(valid_len - 1 - accepted, 0)

    # pass 2 (cheap, no attention): accumulate exactly the accepted
    # queries' masses in sequential order, then un-append the rejects
    def commit(carry, xs):
        a_sl, m_sl, ks = xs
        pieces = []
        for j in range(len(aps)):
            lc = jax.tree.map(lambda t: t[j], a_sl)
            mj = m_sl[j]                                   # [B, L, S+W]

            def acc_one(lc, t):
                gate = (t <= accepted) & (t < valid_len)
                return kvcache.accumulate_scores(
                    lc, spec, mj[:, t], key=ks[j, 1], gate=gate), None

            lc, _ = jax.lax.scan(acc_one, lc, jnp.arange(Lseg))
            pieces.append(kvcache.truncate_rows(lc, spec, n_drop))
        return carry, jax.tree.map(lambda *xs: jnp.stack(xs), *pieces)

    _, attn_c = jax.lax.scan(commit, 0, (attn_c, masses, keys))
    return y, accepted, ModelCache(attn_c, cache.ssm, cache.cross_k,
                                   cache.cross_v, cache.cross_bias)


# ---------------------------------------------------------------------------
# Cache construction (serving init & dry-run specs)
# ---------------------------------------------------------------------------


def init_cache(cfg, spec: CacheSpec, batch: int, max_len: int, *,
               src_len: int = 0, as_spec: bool = False,
               layer_budgets: Optional[Array] = None,
               paged: bool = False, block_len: int = 16,
               pool_blocks: Optional[int] = None) -> ModelCache:
    sb, n_sb, kinds = sb_layout(cfg)
    aps, sps = attn_positions(cfg), ssm_positions(cfg)
    attn_c = ssm_c = None
    if paged and cfg.is_mla:
        raise ValueError("a paged latent (MLA) pool is not supported: "
                         "serve latent attention from the dense store")
    if aps:
        if paged:
            # block-table substrate: per-layer pools + shared table
            # (core/paging.py); serving init only — dry-run specs and the
            # wave engine stay dense
            from repro.core import paging
            assert not as_spec, "paged cache has no as_spec path"
            S = spec.main_store_len(max_len)
            bl = paging.resolve_block_len(spec, S, block_len)
            nb = pool_blocks if pool_blocks else batch * (S // bl)
            one = paging.stacked_paged_kv(
                spec, len(aps), batch, max_len, cfg.num_kv_heads,
                cfg.head_dim, n_blocks=nb, block_len=bl, dtype=cfg.dtype)
        else:
            H, D, Dv = kv_store_dims(cfg)
            one = kvcache.stacked_kv(
                spec, len(aps), batch, max_len, H, D, cfg.dtype,
                as_spec=as_spec, v_dim=Dv)
        if as_spec:
            attn_c = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n_sb, *s.shape), s.dtype), one)
        else:
            attn_c = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n_sb, *x.shape)),
                one)
        if layer_budgets is not None:
            lb = jnp.asarray(layer_budgets, jnp.int32).reshape(n_sb, len(aps))
            if not as_spec:
                attn_c = attn_c._replace(budget=lb)
    if sps:
        one = kvcache.init_ssm_state(
            batch, ssm_lib.conv_dim(cfg), cfg.ssm.d_conv, cfg.ssm_heads,
            cfg.ssm.head_dim, cfg.ssm.d_state, as_spec=as_spec,
            dtype=cfg.dtype)
        def stack2(s):
            if as_spec:
                return jax.ShapeDtypeStruct((n_sb, len(sps), *s.shape), s.dtype)
            return jnp.broadcast_to(s[None, None],
                                    (n_sb, len(sps), *s.shape)).copy()
        ssm_c = jax.tree.map(stack2, one)
    ck = cv = cb = None
    if cfg.is_encoder_decoder and src_len > 0:
        shape_k = (cfg.num_layers, batch, src_len, cfg.num_kv_heads,
                   cfg.head_dim)
        if as_spec:
            ck = jax.ShapeDtypeStruct(shape_k, cfg.dtype)
            cv = jax.ShapeDtypeStruct(shape_k, cfg.dtype)
            cb = jax.ShapeDtypeStruct((batch, src_len), jnp.float32)
        else:
            ck = jnp.zeros(shape_k, cfg.dtype)
            cv = jnp.zeros(shape_k, cfg.dtype)
            cb = jnp.zeros((batch, src_len), jnp.float32)
    moe = None
    if cfg.is_moe:
        moe = (jax.eval_shape(MoEStats.zero) if as_spec
               else MoEStats.zero())
    return ModelCache(attn_c, ssm_c, ck, cv, cb, moe)
