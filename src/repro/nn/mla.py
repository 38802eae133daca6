"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), with
`q_lora_rank` null as in DeepSeek-V2-Lite.

Per token, with H heads, latent rank r, rope width dr, nope width dn and
value width dv:

    q = x Wq                          -> q_nope [H, dn], q_pe [H, dr]
    [c, k_pe] = x Wkva                -> c [r], k_pe [dr] (one for all heads)
    c = rmsnorm(c)
    [k_nope, v] = c Wkvb              -> [H, dn], [H, dv]
    q_pe, k_pe rotated (YaRN, interleaved pairs)
    o_h = softmax(q_h . [k_nope_h, k_pe] * scale) v_h;  out = o Wo

The cache holds only the latent row [c, k_pe] (r + dr values a token,
for all heads), zero-padded to `cfg.latent_store_dim` lanes (`rows` below
are those stored rows). Two paths compute the same attention:

  * expanded (prompt): the latent rows are expanded to per-head K of
    dn + dr and V of dv, and ordinary causal attention runs over them;
  * absorbed (decode): W_UK (Wkvb's key part) is folded into the query,
    q_lat_h = q_nope_h W_UK_h^T, so a score is [q_lat_h, q_pe_h] . [c,
    k_pe] over the whole row; the output stays in latent space, o_lat_h
    = softmax(..) c, and W_UV (Wkvb's value part) maps it back. This is
    MQA over the latent rows: every head reads the same row, and the V
    row is the K row's first r lanes (`kernels/decode_mla`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.cache import LayerKV
from repro.nn import layers as L
from repro.nn.rope import apply_rope, yarn_mscale

Array = jax.Array
NEG_INF = -1e30


def mla_init(key, cfg) -> dict:
    H, r = cfg.num_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    lin = lambda k, a, b: L.linear_init(k, a, b, bias=False, dtype=cfg.dtype)
    return {
        "wq": lin(kq, cfg.d_model, H * cfg.head_dim),
        "wkva": lin(ka, cfg.d_model, cfg.latent_dim),
        "kva_norm": L.rmsnorm_init(r, cfg.dtype),
        "wkvb": lin(kb, r, H * (dn + dv)),
        "wo": lin(ko, H * dv, cfg.d_model),
    }


def softmax_scale(cfg) -> float:
    """(dn + dr)^-1/2, times m(mscale_all_dim)^2 under YaRN."""
    s = cfg.head_dim ** -0.5
    y = cfg.rope_yarn
    if y is not None and y.mscale_all_dim:
        s *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return s


def _rope(x: Array, positions: Array, cfg) -> Array:
    return apply_rope(x, positions, cfg.rope_theta, yarn=cfg.rope_yarn,
                      interleaved=True)


def _positions(positions: Optional[Array], T: int) -> Array:
    return jnp.arange(T)[None] if positions is None else positions


def latent_rows(p: dict, h: Array, cfg, positions: Optional[Array]) -> Array:
    """h [B, T, d_model] -> the cached rows [B, T, latent_store_dim]: the
    normed latent, the rotated rope key, zeros."""
    r = cfg.kv_lora_rank
    a = L.linear(p["wkva"], h)
    c = L.rmsnorm(p["kva_norm"], a[..., :r], cfg.norm_eps)
    pos = _positions(positions, h.shape[1])
    k_pe = _rope(a[..., None, r:], pos, cfg)[..., 0, :]
    pad = jnp.zeros((*c.shape[:-1], cfg.latent_store_dim - cfg.latent_dim),
                    c.dtype)
    return jnp.concatenate([c, k_pe.astype(c.dtype), pad], axis=-1)


def queries(p: dict, h: Array, cfg, positions: Optional[Array]):
    """(q_nope [B, T, H, dn], q_pe [B, T, H, dr] rotated)."""
    B, T, _ = h.shape
    dn = cfg.qk_nope_head_dim
    q = L.linear(p["wq"], h).reshape(B, T, cfg.num_heads, cfg.head_dim)
    return q[..., :dn], _rope(q[..., dn:], _positions(positions, T), cfg)


def _wkvb(p: dict, cfg) -> Array:
    """Wkvb as [r, H, dn + dv] (per head: key part, then value part)."""
    return p["wkvb"]["w"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                                  cfg.qk_nope_head_dim + cfg.v_head_dim)


def expand(p: dict, rows: Array, cfg) -> tuple[Array, Array]:
    """Latent rows [B, S, latent_store_dim] -> (K [B, S, H, dn + dr], V
    [B, S, H, dv]); the rope key is shared by all heads."""
    B, S, _ = rows.shape
    r, H, dn = cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim
    kv = L.linear(p["wkvb"], rows[..., :r]).reshape(B, S, H, -1)
    k_pe = jnp.broadcast_to(rows[:, :, None, r:cfg.latent_dim],
                            (B, S, H, cfg.qk_rope_head_dim))
    return jnp.concatenate([kv[..., :dn], k_pe], axis=-1), kv[..., dn:]


def attend_expanded(p: dict, q_nope: Array, q_pe: Array, rows: Array, cfg,
                    q_positions: Optional[Array] = None) -> Array:
    """Causal attention of queries [B, T, H, .] at `q_positions` (default
    0..T-1) over the expanded latent rows [B, S, .] at positions 0..S-1.
    Returns [B, T, H * dv]."""
    from repro.nn.attention import gqa_attention
    k, v = expand(p, rows, cfg)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    o = gqa_attention(q, k, v, causal=True, q_positions=q_positions,
                      scale=softmax_scale(cfg))
    return o.reshape(*o.shape[:2], -1)


def absorb_query(p: dict, q_nope: Array, q_pe: Array, cfg) -> Array:
    """[q_nope W_UK^T, q_pe, zeros] per head, laid out as a stored row:
    [B, T, H, latent_store_dim]."""
    w_uk = _wkvb(p, cfg)[..., :cfg.qk_nope_head_dim]
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    pad = jnp.zeros((*q_pe.shape[:-1], cfg.latent_store_dim - cfg.latent_dim),
                    q_pe.dtype)
    return jnp.concatenate([q_lat.astype(q_nope.dtype), q_pe, pad], axis=-1)


def absorbed_output(p: dict, o_lat: Array, cfg) -> Array:
    """Latent outputs [B, T, H, r] -> [B, T, H * dv] through W_UV."""
    w_uv = _wkvb(p, cfg)[..., cfg.qk_nope_head_dim:]
    o = jnp.einsum("bthr,rhv->bthv", o_lat.astype(w_uv.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    return o.astype(w_uv.dtype).reshape(*o.shape[:2], -1)


def decode_attention(q: Array, lc: LayerKV, cfg, *,
                     layer: Optional[Array] = None,
                     use_kernels: Optional[bool] = None,
                     interpret: Optional[bool] = None) -> Array:
    """Absorbed decode attention. q [B, H, D] (`absorb_query`); `lc.k`
    holds the latent rows [B, S, D] (or the layer stack [L, B, S, D],
    read at `layer` where it lies), D = latent_store_dim. Rows below
    min(length, budget) are valid. Returns o_lat [B, H, r] float32."""
    from repro.nn.attention import resolve_use_kernels
    n_valid = jnp.minimum(lc.length, lc.budget).astype(jnp.int32)
    store = lc.k
    scale = softmax_scale(cfg)
    if resolve_use_kernels(use_kernels):
        from repro.kernels.decode_mla import ops as mla_ops
        return mla_ops.decode_mla(q, store, n_valid, layer=layer,
                                  kv_lora=cfg.kv_lora_rank, scale=scale,
                                  interpret=interpret)
    from repro.kernels.decode_mla import ref as mla_ref
    if layer is not None:
        store = store[layer]
    return mla_ref.decode_mla_ref(q, store, n_valid,
                                  kv_lora=cfg.kv_lora_rank, scale=scale)
