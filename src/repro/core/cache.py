"""KV-cache data structures with first-class compression.

The cache is the survey's subject: a fixed-*physical*-budget store per
attention layer (static shapes — the TPU adaptation of the GPU systems'
dynamic page tables, DESIGN.md §7.1/§7.3), composed of

  * a **main store** of ``budget`` token slots — bf16, or int-quantized in
    the KIVI layout (K per-channel grouped / V per-token) when
    ``spec.bits < 16``;
  * an optional full-precision **residual ring** of ``window`` recent
    tokens (KIVI's residual; also the "local" window every eviction
    policy protects);
  * per-slot metadata: absolute position, accumulated attention mass
    (H2O/NACL/Keyformer statistics).

Layer-stacked leaves (leading L dim) slice cleanly through
``jax.lax.scan`` over layers; per-layer *logical* budgets (PyramidInfer /
SqueezeAttention / ZigZagKV) mask within the uniform physical budget.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import quantization as qz

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Static spec
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CacheSpec:
    """Static description of one model's KV cache + compression policy.

    budget:  physical main-store slots per layer (0 => uncompressed: the
             main store holds the whole max_len).
    window:  full-precision residual ring (recent tokens). When bits<16
             this doubles as the quantization flush group, so
             ``bits < 16 => group == window``.
    sinks:   protected attention-sink slots (StreamingLLM).
    bits:    16 (dense) / 8 / 4 / 2 for the main store.
    group:   seq-axis group for per-channel K scales.
    policy:  "none" | "streaming" | "h2o" | "nacl" | "keyformer".
    recent_protect: slots whose absolute position is within this many of
             the head are never evicted (H2O's local window).
    """

    budget: int = 0
    window: int = 0
    sinks: int = 4
    bits: int = 16
    group: int = 64
    policy: str = "none"
    recent_protect: int = 64
    nacl_temperature: float = 0.0   # >0: NACL random-eviction mixing
    keyformer_tau: float = 0.0      # >0: gumbel noise at score accumulation

    def __post_init__(self):
        if self.bits < 16:
            assert self.window > 0 and self.group == self.window, (
                "quantized decode path flushes the residual ring as one "
                "per-channel group: require group == window"
            )
        if self.budget:
            assert self.budget % max(self.group, 1) == 0 or self.bits == 16

    @property
    def quantized(self) -> bool:
        return self.bits < 16

    @property
    def compressed(self) -> bool:
        return self.budget > 0

    def main_store_len(self, max_len: int) -> int:
        return self.budget if self.budget else max_len

    def track_scores(self) -> bool:
        return self.policy in ("h2o", "nacl", "keyformer")


FULL = CacheSpec()  # uncompressed baseline


# ---------------------------------------------------------------------------
# Pytree
# ---------------------------------------------------------------------------


class LayerKV(NamedTuple):
    """One attention layer's cache. In the model, every leaf carries a
    leading layer dim and `jax.lax.scan` slices it; all fields are arrays
    (no Nones) so tree structure is static — unused parts have size-0 or
    size-1 placeholder dims.

    Quantized mode stores **bit-packed** codes: k/v trailing dim is
    D·bits/8 int8 (2/4/8-bit lanes, little-endian within the byte — the
    same layout as kernels/kvquant), so physical cache bytes equal the
    logical compressed size."""

    k: Array            # [B, S, H, D] bf16 | [B, S, H, D*bits/8] int8
    v: Array            # [B, S, H, D]
    k_scale: Array      # [B, S//G, H, D] f32 (bits<16) else [B,0,H,D]
    k_zero: Array
    v_scale: Array      # [B, S, H] f32 (bits<16) else [B,0,H]
    v_zero: Array
    rk: Array           # [B, W, H, D] residual ring (W may be 0)
    rv: Array
    r_scores: Array     # [B, W] f32
    scores: Array       # [B, S] f32 accumulated attention mass
    slot_pos: Array     # [B, S] int32, -1 = empty
    length: Array       # [B] int32 valid slots in main store
    rlen: Array         # [B] int32 valid slots in residual
    pos: Array          # [B] int32 absolute next position
    budget: Array       # [] int32 logical per-layer budget (<= S physical)


def init_layer_kv(
    spec: CacheSpec, batch: int, max_len: int, kv_heads: int, head_dim: int,
    dtype=jnp.bfloat16, *, as_spec: bool = False, logical_budget: int | None = None,
    v_dim: Optional[int] = None,
) -> LayerKV:
    """Zeros (or ShapeDtypeStructs when as_spec=True) for one layer.

    v_dim: width of the V store, default `head_dim`. A latent (MLA)
    store (`kv_heads` 0) has no head axis and no V of its own: its rows
    are k [B, S, head_dim] and v is [B, S, 0]; a row's value is its own
    first r lanes (`is_latent`). Without a head axis of 1 the TPU lays
    the rows out whole, and a kernel reads them in place."""
    S = spec.main_store_len(max_len)
    W = spec.window
    G = spec.group if spec.quantized else max(spec.group, 1)
    SG = S // G if spec.quantized else 0
    store_dt = jnp.int8 if spec.quantized else dtype
    B, H, D = batch, kv_heads, head_dim
    Dv = D if v_dim is None else v_dim
    Dp = D * spec.bits // 8 if spec.quantized else D  # packed trailing dim
    Dvp = Dv * spec.bits // 8 if spec.quantized else Dv
    hd = (H,) if H else ()          # a latent store has no head axis

    def mk(shape, dt):
        if as_spec:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    def mkfull(shape, dt, val):
        if as_spec:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.full(shape, val, dt)

    lb = logical_budget if logical_budget is not None else S
    return LayerKV(
        k=mk((B, S, *hd, Dp), store_dt),
        v=mk((B, S, *hd, Dvp), store_dt),
        k_scale=mk((B, SG, *hd, D), jnp.float32),
        k_zero=mk((B, SG, *hd, D), jnp.float32),
        v_scale=mk((B, S if spec.quantized else 0, *hd), jnp.float32),
        v_zero=mk((B, S if spec.quantized else 0, *hd), jnp.float32),
        rk=mk((B, W, *hd, D), dtype),
        rv=mk((B, W, *hd, Dv), dtype),
        r_scores=mk((B, W), jnp.float32),
        scores=mk((B, S), jnp.float32),
        slot_pos=mkfull((B, S), jnp.int32, -1),
        length=mk((B,), jnp.int32),
        rlen=mk((B,), jnp.int32),
        pos=mk((B,), jnp.int32),
        budget=(jax.ShapeDtypeStruct((), jnp.int32) if as_spec
                else jnp.asarray(lb, jnp.int32)),
    )


def stacked_kv(
    spec: CacheSpec, n_layers: int, batch: int, max_len: int, kv_heads: int,
    head_dim: int, dtype=jnp.bfloat16, *, as_spec: bool = False,
    layer_budgets: Optional[Array] = None, v_dim: Optional[int] = None,
) -> LayerKV:
    """Layer-stacked cache: every leaf gets a leading [n_layers] dim."""
    one = init_layer_kv(spec, batch, max_len, kv_heads, head_dim, dtype,
                        as_spec=as_spec, v_dim=v_dim)
    if as_spec:
        stacked = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_layers, *s.shape), s.dtype), one
        )
    else:
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_layers, *x.shape)), one
        )
        if layer_budgets is not None:
            stacked = stacked._replace(budget=layer_budgets.astype(jnp.int32))
        else:
            S = spec.main_store_len(max_len)
            stacked = stacked._replace(
                budget=jnp.full((n_layers,), S, jnp.int32))
    return stacked


# ---------------------------------------------------------------------------
# Views for attention: (K, V, additive mask) over main store + residual
# ---------------------------------------------------------------------------


def is_latent(lc) -> bool:
    """A latent (MLA) store: rows in `k`, no V store of its own."""
    return isinstance(lc, LayerKV) and lc.v.shape[-1] == 0


def validity_bias(lc: LayerKV) -> Array:
    """[B, S+W] additive bias over [main | residual]: 0 where the slot
    holds a live token (within `length`/`budget` for the main store,
    within `rlen` for the ring), -inf elsewhere. This is the ragged-shape
    encoding both decode paths share: the pure-jnp `materialize` oracle
    and the fused Pallas kernel consume the same bias."""
    B, S = lc.slot_pos.shape
    idx = jnp.arange(S)[None]                                   # [1, S]
    main_valid = (idx < jnp.minimum(lc.length, lc.budget)[:, None])
    bias = jnp.where(main_valid, 0.0, NEG_INF).astype(jnp.float32)
    if lc.rk.shape[1] > 0:
        ridx = jnp.arange(lc.rk.shape[1])[None]
        r_valid = ridx < lc.rlen[:, None]
        bias_r = jnp.where(r_valid, 0.0, NEG_INF).astype(jnp.float32)
        bias = jnp.concatenate([bias, bias_r], axis=1)
    return bias


def materialize(lc: LayerKV, spec: CacheSpec, dtype=jnp.bfloat16):
    """Return (k, v, bias) over the concatenated [main | residual] axis.

    k, v: [B, S+W, H, D]; bias: [B, S+W] additive (0 valid / -inf empty).
    Convenience wrapper: `materialize_kv` + `validity_bias` (callers that
    already hold the bias should call `materialize_kv` directly).
    """
    k, v = materialize_kv(lc, spec, dtype)
    return k, v, validity_bias(lc)


def materialize_kv(lc: LayerKV, spec: CacheSpec, dtype=jnp.bfloat16):
    """Dense (k, v) [B, S+W, H, D] over [main | residual].

    The pure-jnp path dequantizes the whole main store **every call** —
    this is the decode oracle. The fused Pallas kernel
    (`repro.kernels.decode_qattn.decode_attention_fused`, dispatched by
    `nn.attention.decode_attention` under `use_kernels`) reads the packed
    codes directly and never materializes this tensor.
    """
    if not isinstance(lc, LayerKV):
        # paged store: gather the slot's blocks into the dense per-slot
        # view first (the parity/oracle path; the paged Pallas kernel
        # walks the block table without this gather)
        from repro.core import paging
        lc = paging.gather_dense(lc, spec)
    B, S, H, _ = lc.k.shape
    if spec.quantized:
        G = spec.group
        D = lc.k_scale.shape[-1]
        k_codes = qz.unpack_codes(lc.k, spec.bits, D)      # [B, S, H, D]
        v_codes = qz.unpack_codes(lc.v, spec.bits, D)
        kq = qz.Quantized(
            k_codes.reshape(B, S // G, G, H, D),
            lc.k_scale[:, :, None],
            lc.k_zero[:, :, None],
        )
        k = kq.dequantize(dtype).reshape(B, S, H, D)
        vq = qz.Quantized(v_codes, lc.v_scale[..., None],
                          lc.v_zero[..., None])
        v = vq.dequantize(dtype)
    else:
        k, v = lc.k.astype(dtype), lc.v.astype(dtype)

    if lc.rk.shape[1] > 0:
        k = jnp.concatenate([k, lc.rk.astype(dtype)], axis=1)
        v = jnp.concatenate([v, lc.rv.astype(dtype)], axis=1)
    return k, v


# ---------------------------------------------------------------------------
# Victim selection (selective-compression family, survey §2)
# ---------------------------------------------------------------------------


def _evictable_mask(lc: LayerKV, spec: CacheSpec) -> Array:
    """[B, S] True where a slot may be evicted."""
    occupied = lc.slot_pos >= 0
    sink = lc.slot_pos < spec.sinks
    recent = lc.slot_pos >= (lc.pos[:, None] - spec.recent_protect)
    return occupied & ~sink & ~recent


def select_victim(lc: LayerKV, spec: CacheSpec, key: Optional[Array]) -> Array:
    """[B] slot index to overwrite, per policy."""
    evictable = _evictable_mask(lc, spec)
    if spec.policy in ("none", "streaming"):
        # oldest evictable slot (sink+window streaming eviction)
        crit = jnp.where(evictable, lc.slot_pos, jnp.iinfo(jnp.int32).max)
        victim = jnp.argmin(crit, axis=-1)
    else:
        score = lc.scores
        if spec.policy == "nacl" and spec.nacl_temperature > 0 and key is not None:
            g = jax.random.gumbel(key, lc.scores.shape, jnp.float32)
            score = score + spec.nacl_temperature * g
        crit = jnp.where(evictable, score, jnp.inf)
        victim = jnp.argmin(crit, axis=-1)
    # Degenerate case (budget <= sinks + recent_protect): nothing is
    # evictable, the criterion is constant, and argmin would return slot 0
    # — silently clobbering a protected attention sink. Relax the recency
    # protection instead: evict the oldest non-sink slot; if every occupied
    # slot holds a sink, take the last physical slot rather than sink 0.
    occupied = lc.slot_pos >= 0
    non_sink = occupied & (lc.slot_pos >= spec.sinks)
    fb_crit = jnp.where(non_sink, lc.slot_pos, jnp.iinfo(jnp.int32).max)
    fallback = jnp.where(jnp.any(non_sink, axis=-1),
                         jnp.argmin(fb_crit, axis=-1),
                         lc.slot_pos.shape[-1] - 1)
    return jnp.where(jnp.any(evictable, axis=-1), victim, fallback)


def _put_rows(arr: Array, slot: Array, val: Array) -> Array:
    """arr: [B, S, ...]; slot: [B]; val: [B, ...] -> write val at [b, slot[b]]."""
    def one(a, s, v):
        return jax.lax.dynamic_update_slice_in_dim(a, v[None], s, axis=0)
    return jax.vmap(one)(arr, slot, val)


def _put_rows_masked(arr: Array, slot: Array, val: Array,
                     mask: Optional[Array]) -> Array:
    """`_put_rows` with a per-sequence gate: row b keeps its old value
    where ``mask[b]`` is False. The gate stays O(row) — the old row is
    gathered and written back — rather than selecting across the whole
    array (a masked append must not cost full-cache bandwidth)."""
    if mask is None:
        return _put_rows(arr, slot, val)

    def one(a, s, v, m):
        old = jax.lax.dynamic_slice_in_dim(a, s, 1, axis=0)
        new = jnp.where(m, v[None].astype(a.dtype), old)
        return jax.lax.dynamic_update_slice_in_dim(a, new, s, axis=0)

    return jax.vmap(one)(arr, slot, val, mask)


def _sel_rows(mask: Optional[Array], new: Array, old: Array) -> Array:
    """Per-sequence select on small [B]-leading metadata leaves."""
    if mask is None:
        return new
    return jnp.where(mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)


# ---------------------------------------------------------------------------
# Per-slot cache surgery (continuous batching): one sequence enters or
# leaves batch position `slot_idx` of a live stacked cache without
# recompiling or reallocating the cache.
# ---------------------------------------------------------------------------


def _scatter_batch(dst: Array, src: Array, slot_idx, batch_axis: int) -> Array:
    """Write `src` (size 1 at `batch_axis`) into `dst` at `slot_idx`."""
    return jax.lax.dynamic_update_slice_in_dim(
        dst, src.astype(dst.dtype), slot_idx, axis=batch_axis)


def insert_request_tree(stacked, slot_idx, prefilled, *, batch_axis: int):
    """Generic pytree scatter: every leaf of `prefilled` (batch 1 at
    `batch_axis`) replaces batch position `slot_idx` of `stacked`."""
    return jax.tree.map(
        lambda d, s: _scatter_batch(d, s, slot_idx, batch_axis),
        stacked, prefilled)


def reset_slot_tree(stacked, slot_idx, *, batch_axis: int, fill=0.0):
    """Generic pytree clear of batch position `slot_idx`."""
    def z(d):
        shape = list(d.shape)
        shape[batch_axis] = 1
        return jax.lax.dynamic_update_slice_in_dim(
            d, jnp.full(shape, fill, d.dtype), slot_idx, axis=batch_axis)
    return jax.tree.map(z, stacked)


def insert_request(stacked: LayerKV, slot_idx, prefilled: LayerKV, *,
                   batch_axis: int = 1) -> LayerKV:
    """Scatter one request's prefilled LayerKV (batch size 1 at
    `batch_axis`) into batch position `slot_idx` of a live stacked cache.

    Every per-sequence leaf is written — main store K/V (dense or packed
    codes), quantized scales/zeros, the residual ring, scores, slot
    positions, lengths, ring lengths, absolute positions. `budget` is
    per-layer state shared by all slots (no batch dim) and belongs to the
    live cache, so it is left untouched. Works on `stacked_kv` output
    (leading [n_layers] dim -> batch_axis=1) and on `ModelCache.attn`
    leaves (leading [n_sb, nA] dims -> batch_axis=2)."""
    upd = {
        f: _scatter_batch(getattr(stacked, f), getattr(prefilled, f),
                          slot_idx, batch_axis)
        for f in LayerKV._fields if f != "budget"
    }
    return stacked._replace(**upd)


def reset_slot(stacked: LayerKV, slot_idx, *, batch_axis: int = 1) -> LayerKV:
    """Clear batch position `slot_idx` back to the empty-cache state:
    zeroed stores/scales/ring/scores, slot_pos = -1, length/rlen/pos = 0.
    The next occupant sees exactly what a fresh `init_layer_kv` provides."""
    upd = {}
    for f in LayerKV._fields:
        if f == "budget":
            continue
        leaf = getattr(stacked, f)
        shape = list(leaf.shape)
        shape[batch_axis] = 1
        fill = -1 if f == "slot_pos" else 0
        upd[f] = jax.lax.dynamic_update_slice_in_dim(
            leaf, jnp.full(shape, fill, leaf.dtype), slot_idx,
            axis=batch_axis)
    return stacked._replace(**upd)


# ---------------------------------------------------------------------------
# Decode append (one token) — dense path
# ---------------------------------------------------------------------------


def append_token_dense(
    lc: LayerKV, spec: CacheSpec, k_new: Array, v_new: Array,
    key: Optional[Array] = None, mask: Optional[Array] = None,
    layer: Optional[Array] = None,
) -> LayerKV:
    """k_new/v_new: [B, H, D] (post-RoPE). Fixed-budget eviction append.
    mask: optional [B] bool — rows where it is False are left untouched
    (ragged multi-token appends: speculative drafts, per-row segment
    tails).

    layer: optional int32 scalar — `lc.k`/`lc.v` are then the
    layer-stacked stores [L, B, S, H, D] (every other leaf is this
    layer's), and each row is scattered into layer `layer` in place, so
    the stack is neither sliced nor rebuilt."""
    S = lc.slot_pos.shape[1]
    cap = jnp.minimum(lc.budget, S)
    full = lc.length >= cap
    victim = select_victim(lc, spec, key)
    slot = jnp.where(full, victim, lc.length)
    if layer is None:
        k = _put_rows_masked(lc.k, slot, k_new.astype(lc.k.dtype), mask)
        v = _put_rows_masked(lc.v, slot, v_new.astype(lc.v.dtype), mask)
    else:
        k = _put_layer_rows(lc.k, layer, slot, k_new, mask)
        v = _put_layer_rows(lc.v, layer, slot, v_new, mask)
    return lc._replace(
        k=k, v=v,
        scores=_put_rows_masked(lc.scores, slot,
                                jnp.zeros(lc.scores.shape[:1]), mask),
        slot_pos=_put_rows_masked(lc.slot_pos, slot, lc.pos, mask),
        length=_sel_rows(mask, jnp.minimum(lc.length + 1, cap), lc.length),
        pos=_sel_rows(mask, lc.pos + 1, lc.pos),
    )


def _put_layer_rows(stack: Array, layer: Array, slot: Array, val: Array,
                    mask: Optional[Array]) -> Array:
    """stack [L, B, S, ...]: write val[b] at [layer, b, slot[b]] in place.
    A row gated off by `mask` is sent past the store's end and dropped."""
    B, S = stack.shape[1], stack.shape[2]
    if mask is not None:
        slot = jnp.where(mask, slot, S)
    return stack.at[layer, jnp.arange(B), slot].set(
        val.astype(stack.dtype), mode="drop")


# ---------------------------------------------------------------------------
# Decode append — quantized path (residual ring + group flush)
# ---------------------------------------------------------------------------


def plan_group_flush(lc, spec: CacheSpec, S: int):
    """Shared quantized-flush planning for the dense and paged stores.

    `lc` is any cache pytree carrying the per-slot metadata fields
    (scores/slot_pos/length/pos/budget/rk/rv) — `LayerKV` or
    `paging.PagedLayerKV`. Returns ``(gslot, cap_groups, kq, vq,
    new_pos)``: the destination group slot per row (victim when at
    budget, else the next free group), the group capacity, the packed
    quantized ring (KIVI per-channel K / per-token V), and the absolute
    positions of the flushed tokens."""
    B = lc.scores.shape[0]
    G = spec.group
    W = spec.window
    n_groups = S // G
    cap_groups = jnp.minimum(lc.budget // G, n_groups)
    used_groups = lc.length // G
    at_cap = used_groups >= cap_groups
    # group-granular victim: argmin of summed scores per group
    gscores = lc.scores.reshape(B, n_groups, G).sum(-1)
    gpos = lc.slot_pos.reshape(B, n_groups, G).max(-1)
    occupied = gpos >= 0
    sinkg = jnp.arange(n_groups)[None] == 0          # protect group 0 (sinks)
    evictable = occupied & ~sinkg
    if spec.policy in ("none", "streaming"):
        crit = jnp.where(evictable, gpos, jnp.iinfo(jnp.int32).max)
    else:
        crit = jnp.where(evictable, gscores, jnp.inf)
    victim_g = jnp.argmin(crit, axis=-1)
    gslot = jnp.where(at_cap, victim_g, used_groups)  # [B]

    kq = qz.quantize_k_per_channel(lc.rk, spec.bits, G)   # codes [B,W,H,D]
    vq = qz.quantize_v_per_token(lc.rv, spec.bits)
    kq = kq._replace(q=qz.pack_codes(kq.q, spec.bits))    # -> [B,W,H,Dp]
    vq = vq._replace(q=qz.pack_codes(vq.q, spec.bits))
    new_pos = (lc.pos[:, None] - W + jnp.arange(W)[None]).astype(jnp.int32)
    return gslot, cap_groups, kq, vq, new_pos


def append_token_quantized(
    lc: LayerKV, spec: CacheSpec, k_new: Array, v_new: Array,
    key: Optional[Array] = None, mask: Optional[Array] = None,
) -> LayerKV:
    """Append to the fp residual ring; when it fills (every `window` steps)
    quantize the ring as one per-channel group (KIVI) and flush it into the
    main store — evicting a whole *group* when at budget (TPU adaptation:
    group-granular eviction keeps layouts dense, DESIGN.md §7.3)."""
    W = spec.window
    G = spec.group
    assert W == G and W > 0

    def flush(lc: LayerKV) -> LayerKV:
        B, S, H, _Dp = lc.k.shape
        D = lc.k_scale.shape[-1]          # true head_dim (k is packed)
        n_groups = S // G
        gslot, cap_groups, kq, vq, new_pos = plan_group_flush(lc, spec, S)

        def put_group(arr, gs, val):   # arr [B, n_groups*?...]
            return _put_rows(arr.reshape(B, n_groups, -1), gs,
                             val.reshape(B, -1)).reshape(arr.shape)

        return lc._replace(
            k=put_group(lc.k, gslot, kq.q),
            v=put_group(lc.v, gslot, vq.q),
            k_scale=_put_rows(lc.k_scale, gslot,
                              kq.scale.reshape(B, H, D)),
            k_zero=_put_rows(lc.k_zero, gslot, kq.zero.reshape(B, H, D)),
            v_scale=put_group(lc.v_scale, gslot, vq.scale.reshape(B, W, H)),
            v_zero=put_group(lc.v_zero, gslot, vq.zero.reshape(B, W, H)),
            scores=put_group(lc.scores, gslot, lc.r_scores),
            slot_pos=put_group(lc.slot_pos, gslot, new_pos),
            length=jnp.minimum(lc.length + W, cap_groups * G),
            rlen=jnp.zeros_like(lc.rlen),
            r_scores=jnp.zeros_like(lc.r_scores),
        )

    # Per-row flush: under continuous batching, sequences in one stacked
    # cache sit at different ring phases, so a batch-wide `jnp.all` gate
    # would stall a full ring until its neighbours catch up (and the next
    # append would clamp out of bounds, corrupting the newest ring slot).
    # Flush exactly the rows whose ring is full; skip the work entirely
    # when none is (the common wave-lockstep / mid-window case). A
    # masked-out row must not flush either — its append never happens,
    # so neither do the append's side effects.
    need = lc.rlen >= W                                   # [B]
    if mask is not None:
        need = need & mask

    def flush_rows(lc: LayerKV) -> LayerKV:
        flushed = flush(lc)
        def sel(f, o):
            return jnp.where(need.reshape((-1,) + (1,) * (f.ndim - 1)), f, o)
        upd = {fld: sel(getattr(flushed, fld), getattr(lc, fld))
               for fld in LayerKV._fields if fld != "budget"}
        return lc._replace(**upd)

    lc = jax.lax.cond(jnp.any(need), flush_rows, lambda c: c, lc)
    # ring append at rlen (row-gated by mask: untouched rows keep their
    # ring tail and counters)
    return lc._replace(
        rk=_put_rows_masked(lc.rk, lc.rlen, k_new.astype(lc.rk.dtype), mask),
        rv=_put_rows_masked(lc.rv, lc.rlen, v_new.astype(lc.rv.dtype), mask),
        r_scores=_put_rows_masked(lc.r_scores, lc.rlen,
                                  jnp.zeros(lc.r_scores.shape[:1]), mask),
        rlen=_sel_rows(mask, lc.rlen + 1, lc.rlen),
        pos=_sel_rows(mask, lc.pos + 1, lc.pos),
    )


def append_token(lc, spec: CacheSpec, k_new: Array, v_new: Array,
                 key: Optional[Array] = None, mask: Optional[Array] = None,
                 layer: Optional[Array] = None):
    """One token's append to any store; `layer` as in
    `append_token_dense` (dense stores only)."""
    if layer is not None:
        return append_token_dense(lc, spec, k_new, v_new, key, mask, layer)
    if not isinstance(lc, LayerKV):
        # paged store (core/paging.py): same eviction/flush semantics,
        # writes routed through the block table
        from repro.core import paging
        return paging.append_token_paged(lc, spec, k_new, v_new, key=key,
                                         mask=mask)
    if spec.quantized:
        return append_token_quantized(lc, spec, k_new, v_new, key, mask)
    return append_token_dense(lc, spec, k_new, v_new, key, mask)


def append_segment(lc, spec: CacheSpec, k_seg: Array, v_seg: Array,
                   key: Optional[Array] = None,
                   valid_len: Optional[Array] = None):
    """Append `n` tokens in order: k_seg/v_seg [B, n, H, D] (post-RoPE).

    The multi-token generalization of `append_token` — one call per
    prompt segment or speculative draft instead of one per token. It is
    *bit-compatible with the monolithic path by construction*: the body
    is a `lax.scan` of `append_token` over the segment, so evictions and
    quantized group flushes fire at exactly the token positions they
    would in a token-at-a-time loop (a segment-granular bulk write could
    not reproduce mid-segment victim selection). Works on both stores —
    `LayerKV` and `paging.PagedLayerKV` ride through `append_token`'s
    dispatch (segment writes scatter through the block table there).

    `valid_len`: optional [B] int32 ragged lengths — row b appends only
    its first `valid_len[b]` tokens (speculative verify segments differ
    per slot; inactive slots pass 0). Bit-equal per row to appending
    that row's prefix alone.

    `key` is split once per token (policy noise, e.g. NACL), matching a
    caller that splits its own key per step."""
    n = k_seg.shape[1]
    if n == 0:
        return lc
    keys = (jax.random.split(key, n) if key is not None
            else jnp.zeros((n, 0), jnp.uint32))

    def body(c, xs):
        k1, v1, kk, t = xs
        m = (t < valid_len) if valid_len is not None else None
        return append_token(c, spec, k1, v1,
                            key=kk if key is not None else None,
                            mask=m), None

    lc, _ = jax.lax.scan(
        body, lc, (k_seg.transpose(1, 0, 2, 3), v_seg.transpose(1, 0, 2, 3),
                   keys, jnp.arange(n)))
    return lc


# ---------------------------------------------------------------------------
# Speculative rollback: un-append the most recent tokens
# ---------------------------------------------------------------------------


def truncate_rows(lc, spec: CacheSpec, n_drop: Array):
    """Un-append the `n_drop[b]` most recently appended tokens of row b
    (rejected speculative drafts). n_drop: [B] int32, 0 = keep row as is.

    The rollback contract (enforced by the speculative engine's per-slot
    depth cap, `serving.speculative`): the appends being undone must not
    have crossed an eviction or a quantized group-flush boundary —

      * dense stores: the rolled-back appends landed on *fresh* slots
        (`length < cap` throughout), so rollback is a length/pos
        decrement plus clearing the dropped rows' metadata (slot_pos ->
        -1, scores -> 0 — stale score mass left behind would bias the
        next `select_victim` toward/away from a row that no longer holds
        that token);
      * quantized stores: the rolled-back appends live in the fp
        residual ring (`rlen + n <= window`, no flush fired), so
        rollback is an rlen/pos decrement — ring rows beyond `rlen` are
        masked by the validity bias and fully rewritten before the next
        flush can quantize them, so their stale bytes are unobservable.

    K/V bytes of dropped dense rows are left in place (masked by
    `slot_pos`/`length` exactly like a `reset_slot`'s zeros would be).
    Works on both stores: `LayerKV` and `paging.PagedLayerKV` share the
    metadata fields this touches (pool bytes of dropped paged rows are
    unreachable the same way; the engine returns no-longer-covered
    blocks to the free list host-side)."""
    n_drop = jnp.maximum(n_drop, 0)
    if spec.quantized:
        return lc._replace(rlen=lc.rlen - n_drop, pos=lc.pos - n_drop)
    # leaves may carry leading layer-stacking dims ([..., B] metadata,
    # [..., B, S] per-slot rows): broadcast against the trailing axes so
    # one call serves a per-layer piece and a whole stacked cache alike
    S = lc.scores.shape[-1]
    idx = jnp.arange(S)
    new_len = lc.length - n_drop
    dropped = (idx >= new_len[..., None]) & (idx < lc.length[..., None])
    return lc._replace(
        scores=jnp.where(dropped, 0.0, lc.scores),
        slot_pos=jnp.where(dropped, -1, lc.slot_pos),
        length=new_len,
        pos=lc.pos - n_drop,
    )


# ---------------------------------------------------------------------------
# Score accumulation (H2O / NACL / Keyformer statistics)
# ---------------------------------------------------------------------------


def accumulate_scores(
    lc: LayerKV, spec: CacheSpec, attn_mass: Array, key: Optional[Array] = None,
    gate: Optional[Array] = None,
) -> LayerKV:
    """attn_mass: [B, S+W] — this step's attention probability mass per slot
    (mean over query heads), aligned with `materialize` ordering.

    gate: optional [B] bool — rows where it is False accumulate nothing
    (speculative verify defers accumulation until acceptance is known,
    then applies only the accepted queries' masses; adding an exact 0.0
    keeps the float association chain identical to a row that never saw
    the step). Applied *after* any policy transform, so a gated-out row
    is a true no-op even for keyformer's non-additive scoring."""
    if not spec.track_scores():
        return lc
    S = lc.scores.shape[1]          # main-store length (dense or paged)
    main, resid = attn_mass[:, :S], attn_mass[:, S:]
    if spec.policy == "keyformer" and spec.keyformer_tau > 0 and key is not None:
        g = jax.random.gumbel(key, main.shape, jnp.float32)
        main = jax.nn.softmax(
            (jnp.log(jnp.maximum(main, 1e-9)) + g) / spec.keyformer_tau, axis=-1
        )
    if gate is not None:
        main = jnp.where(gate[:, None], main, 0.0)
        resid = jnp.where(gate[:, None], resid, 0.0)
    lc = lc._replace(scores=lc.scores + main)
    if resid.shape[1] > 0:
        lc = lc._replace(r_scores=lc.r_scores + resid)
    return lc


# ---------------------------------------------------------------------------
# Prefill compression: select `budget` prompt tokens into the cache
# (SnapKV/H2O/NACL prompt-phase; survey §2)
# ---------------------------------------------------------------------------


def compress_prompt(
    spec: CacheSpec, k: Array, v: Array, attn_mass: Array,
    key: Optional[Array] = None, dtype=jnp.bfloat16,
    logical_budget: Optional[Array] = None,
) -> LayerKV:
    """k, v: [B, S_p, H, D] post-RoPE prompt KV (a latent store: k the
    rows [B, S_p, D], v [B, S_p, 0]); attn_mass: [B, S_p]
    accumulated attention mass from the prefill pass. Returns a LayerKV at
    the physical budget (last `window` tokens -> residual ring, fp)."""
    B, S_p = k.shape[:2]
    H, D = k.shape[2:] if k.ndim == 4 else (0, k.shape[2])
    S = spec.main_store_len(S_p)
    W = spec.window
    positions = jnp.broadcast_to(jnp.arange(S_p)[None], (B, S_p))

    if S >= S_p and not spec.quantized and W == 0:
        # no selection needed: place the prompt verbatim (headroom allowed)
        lc = init_layer_kv(spec, B, S_p if spec.budget == 0 else S_p,
                           H, D, dtype, v_dim=v.shape[-1])
        pad = S - S_p
        def padded(x, fill=0):
            return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                           constant_values=fill)
        lb = logical_budget if logical_budget is not None else jnp.asarray(S)
        return lc._replace(
            k=padded(k.astype(lc.k.dtype)), v=padded(v.astype(lc.v.dtype)),
            scores=padded(attn_mass.astype(jnp.float32)),
            slot_pos=padded(positions, fill=-1).astype(jnp.int32),
            length=jnp.full((B,), S_p, jnp.int32),
            pos=jnp.full((B,), S_p, jnp.int32),
            budget=jnp.asarray(lb, jnp.int32).reshape(()),
        )

    # --- policy score over prompt positions -------------------------------
    if spec.policy in ("none", "streaming"):
        score = positions.astype(jnp.float32)          # keep most recent
    else:
        score = attn_mass.astype(jnp.float32)
        if spec.policy == "nacl" and spec.nacl_temperature > 0 and key is not None:
            score = score + spec.nacl_temperature * jax.random.gumbel(
                key, score.shape, jnp.float32)
        if spec.policy == "keyformer" and spec.keyformer_tau > 0 and key is not None:
            g = jax.random.gumbel(key, score.shape, jnp.float32)
            score = (jnp.log(jnp.maximum(score, 1e-9)) + g) / spec.keyformer_tau

    in_resid = positions >= (S_p - W)                   # last W -> residual
    sink = (positions >= 0) & (positions < spec.sinks)
    sel_score = jnp.where(sink, jnp.inf, score)
    sel_score = jnp.where(in_resid, -jnp.inf, sel_score)

    n_main = min(S, S_p - W) if S_p - W > 0 else 0
    n_main = max(n_main, 0)

    # headroom: more physical slots than candidate tokens — pad candidates
    pad_amt = max(0, S + W - S_p)
    if pad_amt:
        def padc(x, fill):
            return jnp.pad(x, ((0, 0), (0, pad_amt)) +
                           ((0, 0),) * (x.ndim - 2), constant_values=fill)
        k = padc(k, 0)
        v = padc(v, 0)
        attn_mass = padc(attn_mass, 0.0)
        positions = padc(positions, -(10 ** 6))
        sel_score = padc(sel_score, -jnp.inf)
    lb = logical_budget if logical_budget is not None else jnp.asarray(S)
    # top-`S` slots (physical); logical budget masks via `length`
    _, idx = jax.lax.top_k(sel_score, S)                # [B, S]
    idx = jnp.sort(idx, axis=-1)                        # keep causal order
    take = lambda x: jnp.take_along_axis(
        x, idx.reshape(B, S, *([1] * (x.ndim - 2))), axis=1)
    k_sel, v_sel = take(k), take(v)
    score_sel = jnp.take_along_axis(attn_mass, idx, axis=1)
    pos_sel = jnp.take_along_axis(positions, idx, axis=1)
    n_valid = jnp.minimum(jnp.asarray(n_main), lb)
    valid = jnp.arange(S)[None] < n_valid               # [1|B, S]
    valid = jnp.broadcast_to(valid, (B, S)) if valid.shape[0] == 1 else valid

    lc = init_layer_kv(spec, B, S_p, H, D, dtype, v_dim=v.shape[-1])
    lc = lc._replace(budget=jnp.asarray(lb, jnp.int32).reshape(()))
    if spec.quantized:
        G = spec.group
        kq = qz.quantize_k_per_channel(k_sel, spec.bits, G)
        vq = qz.quantize_v_per_token(v_sel, spec.bits)
        lc = lc._replace(
            k=qz.pack_codes(kq.q, spec.bits),
            v=qz.pack_codes(vq.q, spec.bits),
            k_scale=kq.scale.squeeze(2), k_zero=kq.zero.squeeze(2),
            v_scale=vq.scale.squeeze(-1), v_zero=vq.zero.squeeze(-1),
        )
    else:
        lc = lc._replace(k=k_sel.astype(lc.k.dtype), v=v_sel.astype(lc.v.dtype))

    lc = lc._replace(
        scores=jnp.where(valid, score_sel, 0.0),
        slot_pos=jnp.where(valid, pos_sel, -1),
        length=jnp.full((B,), 1, jnp.int32) * n_valid.astype(jnp.int32),
        pos=jnp.full((B,), S_p, jnp.int32),
    )
    if W > 0:
        lc = lc._replace(
            rk=k[:, S_p - W:S_p].astype(lc.rk.dtype),
            rv=v[:, S_p - W:S_p].astype(lc.rv.dtype),
            r_scores=attn_mass[:, S_p - W:S_p].astype(jnp.float32),
            rlen=jnp.full((B,), W, jnp.int32),
        )
    return lc


# ---------------------------------------------------------------------------
# SSM / conv state (Mamba2 layers): the attention-free "cache"
# ---------------------------------------------------------------------------


class SSMState(NamedTuple):
    conv: Array    # [B, d_conv-1, conv_dim]
    state: Array   # [B, H, P, N] f32


def init_ssm_state(batch: int, conv_dim: int, d_conv: int, heads: int,
                   head_dim: int, d_state: int, *, as_spec: bool = False,
                   dtype=jnp.bfloat16) -> SSMState:
    mk = (lambda s, dt: jax.ShapeDtypeStruct(s, dt)) if as_spec else (
        lambda s, dt: jnp.zeros(s, dt))
    return SSMState(
        conv=mk((batch, d_conv - 1, conv_dim), dtype),
        state=mk((batch, heads, head_dim, d_state), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Bytes accounting
# ---------------------------------------------------------------------------


def cache_physical_bytes(lc) -> int:
    """Resident bytes of one cache pytree. Dense stores: every leaf is
    per-slot reserved memory, so this is plain `tree_bytes`. Paged stores
    report *allocated-block* bytes — pool rows a slot actually mapped via
    the block table — plus the (small) per-slot metadata, so occupancy
    stats reflect real pool usage rather than the reserved worst case."""
    from repro.utils import tree_bytes
    if not isinstance(lc, LayerKV) and hasattr(lc, "block_tbl"):
        from repro.core import paging
        return paging.paged_physical_bytes(lc)
    return tree_bytes(lc)


def cache_logical_bytes_per_layer(spec: CacheSpec, max_len: int, kv_heads: int,
                                  head_dim: int, base_bytes: float = 2.0) -> float:
    """What the compression actually stores per layer (ratio ground truth)."""
    S = spec.main_store_len(max_len)
    if spec.quantized:
        return qz.kv_logical_bytes(
            S + spec.window, kv_heads, head_dim, bits=spec.bits,
            group=spec.group, residual_window=spec.window,
            base_bytes=base_bytes)
    return 2 * (S + spec.window) * kv_heads * head_dim * base_bytes
