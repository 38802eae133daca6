"""Plain reference of a dense pre-norm decoder (the Llama-style block
both configurations run), in float32 `jax.numpy` at `highest` matmul
precision, with no kernels, cache or batching.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * g1
                q, k, v = h Wq, h Wk, h Wv      rotated by RoPE (half split)
                x += softmax(q k^T / sqrt(D), causal) v  Wo   (GQA)
                h = rmsnorm(x) * g2
                x += (silu(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * gf) E^T               (tied head)

A served request is replayed teacher-forced: its prompt and its served
tokens go through one causal pass, and the logits at the positions that
produced each served token are returned. Where the cell's cache stores
keys and values quantized (KIVI: keys per channel over groups of `g`
positions, values per token, asymmetric min/max), a decode query at
position p >= prompt reads the dequantized rows of every group that was
complete before p (positions below g * floor(p / g)) and the exact rows
after; prompt queries read exact rows, as a prefill does.

`precision="fp8"` rounds every matmul input to float8 e4m3 (per output
channel for weights, per row for activations) before the float32
product: the control, one precision below the bfloat16 the
configurations state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def fp8_round(x, axis):
    """Round to float8 e4m3 values (3 mantissa bits, subnormals below
    2^-6, max 448) after scaling the largest |x| along `axis` to 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    y = x / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = jnp.exp2(e - 3.0)
    return jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) * s


def _mm(x, w, low):
    """x [..., a] @ w [a, b] in float32 (weights per output channel,
    activations per row when `low`)."""
    if low:
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, rotating half-split pairs."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv        # [T, D/2]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def kivi_roundtrip(k, v, bits, group):
    """Quantize and dequantize k, v [T, H, D]: keys per channel over
    `group`-position groups, values per token over the head dimension."""
    levels = (1 << bits) - 1

    def qdq(x, axis):
        lo = jnp.min(x, axis=axis, keepdims=True)
        hi = jnp.max(x, axis=axis, keepdims=True)
        sc = jnp.maximum(hi - lo, 1e-8) / levels
        return jnp.clip(jnp.round((x - lo) / sc), 0, levels) * sc + lo

    T, H, D = k.shape
    kd = qdq(k.reshape(T // group, group, H, D), 1).reshape(T, H, D)
    return kd, qdq(v, -1)


def _attend(q, k, v, kd, vd, prompt, block, low):
    """Causal GQA attention for q [T, Hq, D] over k, v [T, Hkv, D]. With
    quantized twins kd, vd, a block of decode queries (block = group,
    aligned, so the block's queries share their last group boundary i0)
    reads kd, vd below i0 and k, v from i0 on; prompt blocks read k, v."""
    T, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    if low:
        q, k, v = fp8_round(q, -1), fp8_round(k, -1), fp8_round(v, -1)
        if kd is not None:
            kd, vd = fp8_round(kd, -1), fp8_round(vd, -1)
    qg = q.reshape(T, Hkv, G, D) / math.sqrt(D)
    kpos = jnp.arange(T)

    def one(i0):
        qb = jax.lax.dynamic_slice_in_dim(qg, i0, block, 0)    # [b,Hkv,G,D]
        qpos = i0 + jnp.arange(block)
        kb, vb = k, v
        if kd is not None:
            old = ((kpos < i0) & (i0 >= prompt))[:, None, None]
            kb, vb = jnp.where(old, kd, k), jnp.where(old, vd, v)
        s = jnp.einsum("bhgd,thd->hgbt", qb, kb, precision=HI)
        causal = kpos[None] <= qpos[:, None]                    # [b, T]
        p = jax.nn.softmax(jnp.where(causal, s, NEG), -1)
        o = jnp.einsum("hgbt,thd->bhgd", p, vb, precision=HI)
        return o.reshape(block, Hq * D)

    outs = jax.lax.map(one, jnp.arange(0, T, block))
    return outs.reshape(T, Hq * D)


def served_logits(params, m, tokens, prompt, at, *, kv_bits=16, group=0,
                  precision="f32", block=256):
    """Logits [len(at), V] at positions `at` of the causal pass over
    `tokens` [T] (prompt followed by served tokens, padded at the end;
    T a multiple of `block`). `prompt` is the prompt length; the cache is
    quantized when kv_bits < 16, and then `block` must equal `group` and
    the prompt be a multiple of it."""
    low = precision == "fp8"
    if kv_bits < 16 and block != group:
        raise ValueError(f"quantized cache needs block == group, got "
                         f"{block} and {group}")
    eps, theta = m["norm_eps"], m["rope_theta"]
    Hq, Hkv, D = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    T = tokens.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    table = f32(params["embed"]["table"])
    x = table[tokens]

    def layer(x, p):
        p = jax.tree.map(f32, p)
        h = _rmsnorm(x, p["norm1"]["scale"], eps)
        a = p["attn"]
        q = _rope(_mm(h, a["wq"]["w"], low).reshape(T, Hq, D), theta)
        k = _rope(_mm(h, a["wk"]["w"], low).reshape(T, Hkv, D), theta)
        v = _mm(h, a["wv"]["w"], low).reshape(T, Hkv, D)
        kd = vd = None
        if kv_bits < 16:
            kd, vd = kivi_roundtrip(k, v, kv_bits, group)
        o = _attend(q, k, v, kd, vd, prompt, block, low)
        x = x + _mm(o, a["wo"]["w"], low)
        h = _rmsnorm(x, p["norm2"]["scale"], eps)
        f = p["mlp"]
        g = jax.nn.silu(_mm(h, f["gate"]["w"], low)) * _mm(h, f["up"]["w"],
                                                            low)
        return x + _mm(g, f["down"]["w"], low), None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["sub0"])
    h = _rmsnorm(x[at], f32(params["final_norm"]["scale"]), eps)
    head = table.T if m["tie_embeddings"] else f32(params["head"]["w"])
    return _mm(h, head, low)
