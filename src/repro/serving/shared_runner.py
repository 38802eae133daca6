"""KVSharer serving path (survey [10]): layer-wise KV cache sharing.

Sharing crosses layer boundaries, so this runner unrolls the layer loop
in Python (uniform-attention models; the scanned path cannot index
sibling layers' caches). A shared layer performs attention against its
*source* layer's cache and neither computes nor stores its own K/V —
saving cache memory (and the K/V projections) for `len(mapping)/L` of
the layers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import cache as kvcache
from repro.core.cache import CacheSpec, LayerKV
from repro.core import sharing as sharing_lib
from repro.nn import attention as attn
from repro.nn import blocks as B
from repro.nn import layers as L
from repro.nn import model as M

Array = jax.Array


def _layer_params(params, i: int):
    return jax.tree.map(lambda a: a[i], params["blocks"]["sub0"])


def calibrate_sharing(params, cfg, tokens: Array, n_share: int) -> dict[int, int]:
    """Run a short calibration prefill collecting per-layer K/V summaries,
    then build the KVSharer dissimilarity map."""
    spec = CacheSpec(budget=tokens.shape[1] + 1)
    _, cache = M.prefill(params, cfg, {"tokens": tokens}, spec)
    ks = cache.attn.k[:, 0]           # [L, B, S, H, D] (n_sb=1 squeezed)
    vs = cache.attn.v[:, 0]
    summaries = sharing_lib.calibration_summaries(ks, vs)
    return sharing_lib.build_sharing_map(summaries, n_share)


def shared_prefill(params, cfg, batch: dict, spec: CacheSpec,
                   mapping: dict[int, int]):
    """Unrolled prefill; shared layers get no cache entry (None)."""
    tokens = batch["tokens"]
    x = L.embed(params["embed"], tokens)
    Bsz, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (Bsz, T))
    caches: list[Optional[LayerKV]] = []
    for i in range(cfg.num_layers):
        p = _layer_params(params, i)
        if i in mapping:
            # reuse source K/V: attend with own Q against source cache's
            # prompt K/V — here at prefill both equal the full prompt, so
            # recompute attention with the source layer's k/v
            src_piece = caches[mapping[i]]
            h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
            q, _, _ = attn.qkv(p["attn"], h, cfg, positions)
            k, v, bias = kvcache.materialize(src_piece, spec, cfg.dtype)
            o = attn.gqa_attention(
                q, k, v, causal=True, q_positions=positions,
                kv_positions=src_piece.slot_pos, kv_bias=bias)
            x = x + L.linear(p["attn"]["wo"], o.reshape(Bsz, T, -1))
            x, _ = B._ffn(p, x, cfg)
            caches.append(None)
        else:
            x, _, piece = B.block_prefill(p, x, cfg, "attn", spec,
                                          positions=positions)
            caches.append(piece)
    logits = _final_logits(params, cfg, x[:, -1:])
    return logits, caches


def shared_decode_step(params, cfg, caches, token: Array, spec: CacheSpec,
                       mapping: dict[int, int]):
    x = L.embed(params["embed"], token)
    Bsz = token.shape[0]
    new_caches = list(caches)
    for i in range(cfg.num_layers):
        p = _layer_params(params, i)
        if i in mapping:
            src = new_caches[mapping[i]]   # source already appended this step
            h = L.rmsnorm(p["norm1"], x, cfg.norm_eps)
            pos = (src.pos - 1)[:, None]
            q, _, _ = attn.qkv(p["attn"], h, cfg, pos)
            o, _ = attn.decode_attention(q, src, spec, dtype=cfg.dtype,
                                         q_pos=pos[:, 0])
            x = x + L.linear(p["attn"]["wo"], o.reshape(Bsz, 1, -1))
            x, _ = B._ffn(p, x, cfg)
        else:
            x, new_caches[i], _ = B.block_decode(p, x, cfg, "attn", spec,
                                                 new_caches[i])
    logits = _final_logits(params, cfg, x)
    return logits, new_caches


def _final_logits(params, cfg, x):
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], x)[:, 0]
    return L.linear(params["head"], x).astype(jnp.float32)[:, 0]


def cache_bytes_saved(mapping: dict[int, int], n_layers: int) -> float:
    return 1.0 - sharing_lib.shared_bytes_fraction(mapping, n_layers)
