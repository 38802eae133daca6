"""Plain reference of DeepSeek-V2's decoder, in float32 `jax.numpy` at
`highest` matmul precision, with no kernels, cache or batching. It
follows the model's published equations (arXiv:2405.04434, and the
model's modeling_deepseek.py), with H heads, latent rank r, rope width
dr, nope width dn, value width dv:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * g1
                q = h Wq                   -> q_nope [H, dn], q_pe [H, dr]
                [c, k_pe] = h Wkva;  c = rmsnorm(c) * g_kv
                [k_nope, v] = c Wkvb       -> [H, dn], [H, dv]
                q_pe, k_pe rotated (YaRN, interleaved pairs; k_pe shared
                by every head)
                x += softmax([q_nope, q_pe] . [k_nope, k_pe] * s, causal) v  Wo
                h = rmsnorm(x) * g2
                leading layers:  x += (silu(h Wg) * (h Wu)) Wd
                expert layers:   p = softmax(h Wr)  (float32)
                                 w_k, e_k = top-k of p (greedy); w_k *= scale,
                                 renormalized first only if norm_topk_prob
                                 x += sum_k w_k E_{e_k}(h) + S(h)
    logits = (rmsnorm(x) * gf) Wh        (Wh = E^T where the head is tied)

where E_e is expert e's SwiGLU of width moe_intermediate_size, S the
shared experts' SwiGLU of width n_shared_experts times that, and s =
(dn + dr)^-1/2 * m^2 with m = 0.1 mscale_all_dim ln(factor) + 1.

YaRN: for pair i of the rope width, freq_extra = theta^(-2i/dr) and
freq_inter = freq_extra / factor; with d(n) = dr ln(L / (2 pi n)) / (2 ln
theta) at the original context L, low = floor(d(beta_fast)) and high =
ceil(d(beta_slow)), clamped to [0, dr - 1]; ramp = clip((i - low) / (high
- low), 0, 1); inv_freq = freq_inter ramp + freq_extra (1 - ramp); cos and
sin are scaled by m(mscale) / m(mscale_all_dim). Interleaved pairs: a
rope vector is read as (dr/2, 2) and transposed before the half split.

Departures, each for memory or time and none changing the result: the
weights are cast to float32 one layer at a time inside the layer loop
(the bfloat16 weights do not fit twice in float32); attention runs over
blocks of `block` queries; the routed experts are a dense sum over all
experts weighted by the combine weights (zero for experts not picked),
over blocks of `block` tokens.

A served request is replayed teacher-forced, as `refs/dense_decoder.py`
does: prompt and served tokens through one causal pass, the logits at
the positions that produced each served token returned. The cache is
the full-precision latent store, so decode and prompt queries read the
same rows.

`precision="fp8"` rounds every matmul input to float8 e4m3 (per output
channel for weights, per row for activations) before the float32
product: the control, one precision below the configuration's bfloat16.
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
    """x [..., a] @ w [a, b] in float32 (fp8-rounded inputs when `low`)."""
    if low:
        x, w = fp8_round(x, -1), fp8_round(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(m):
    """(inv_freq [dr/2], cos/sin scale, softmax scale) of config `m`."""
    dr, theta = m["qk_rope_head_dim"], float(m["rope_theta"])
    rs = m["rope_scaling"]
    f, L = float(rs["factor"]), rs["original_max_position_embeddings"]
    i = jnp.arange(dr // 2, dtype=jnp.float32)
    extra = 1.0 / theta ** (2 * i / dr)
    inter = extra / f

    def d(n):
        return dr * math.log(L / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(d(rs["beta_fast"])), 0)
    high = min(math.ceil(d(rs["beta_slow"])), dr - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    inv = inter * ramp + extra * (1 - ramp)
    cs = _mscale(f, rs["mscale"]) / _mscale(f, rs["mscale_all_dim"])
    scale = ((m["qk_nope_head_dim"] + dr) ** -0.5
             * _mscale(f, rs["mscale_all_dim"]) ** 2)
    return inv, cs, scale


def _rope(x, inv, cs):
    """x [T, H, dr] at positions 0..T-1: interleaved pairs, half split."""
    T, H, D = x.shape
    x = x.reshape(T, H, D // 2, 2).transpose(0, 1, 3, 2).reshape(T, H, D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv        # [T, D/2]
    c, s = (jnp.cos(ang) * cs)[:, None], (jnp.sin(ang) * cs)[:, None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _attend(q, k, v, scale, block, low):
    """Causal attention of q, k [T, H, dq] and v [T, H, dv] over blocks
    of `block` queries. Returns [T, H * dv]."""
    T, H, _ = q.shape
    if low:
        q, k, v = fp8_round(q, -1), fp8_round(k, -1), fp8_round(v, -1)
    kpos = jnp.arange(T)

    def one(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block, 0)       # [b, H, dq]
        s = jnp.einsum("bhd,thd->hbt", qb, k, precision=HI) * scale
        causal = kpos[None] <= (i0 + jnp.arange(block))[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, NEG), -1)
        return jnp.einsum("hbt,thd->bhd", p, v, precision=HI).reshape(
            block, -1)

    return jax.lax.map(one, jnp.arange(0, T, block)).reshape(T, -1)


def _swiglu(h, f, low):
    return _mm(jax.nn.silu(_mm(h, f["gate"]["w"], low))
               * _mm(h, f["up"]["w"], low), f["down"]["w"], low)


def _experts(h, p, m, block, low):
    """Routed experts, exact: each token's top-k experts by the float32
    softmax router, weighted, as a dense sum over all experts (weight 0
    for those not picked), plus the shared experts."""
    T, d = h.shape
    E, k = m["n_routed_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(h, p["router"], low), -1)
    w, idx = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * float(m["routed_scaling_factor"])
    comb = jnp.sum(jax.nn.one_hot(idx, E) * w[..., None], 1)     # [T, E]
    gate, up, down = p["gate"], p["up"], p["down"]
    if low:
        gate, up, down = (fp8_round(gate, 1), fp8_round(up, 1),
                          fp8_round(down, 1))

    def one(i0):
        hb = jax.lax.dynamic_slice_in_dim(h, i0, block, 0)       # [b, d]
        cb = jax.lax.dynamic_slice_in_dim(comb, i0, block, 0)    # [b, E]
        if low:
            hb = fp8_round(hb, -1)
        g = jnp.einsum("bd,edf->bef", hb, gate, precision=HI)
        u = jnp.einsum("bd,edf->bef", hb, up, precision=HI)
        a = jax.nn.silu(g) * u
        if low:
            a = fp8_round(a, -1)
        a = a * cb[..., None]
        return jnp.einsum("bef,efd->bd", a, down, precision=HI)

    y = jax.lax.map(one, jnp.arange(0, T, block)).reshape(T, d)
    if "shared" in p:
        y = y + _swiglu(h, p["shared"], low)
    return y


def served_logits(params, m, tokens, prompt, at, *, kv_bits=16, group=0,
                  precision="f32", block=256):
    """Logits [len(at), V] at positions `at` of the causal pass over
    `tokens` [T] (prompt followed by served tokens, padded at the end;
    T a multiple of `block`). The latent store is kept in full
    precision, so `kv_bits` must be 16; `prompt` and `group` play no
    part."""
    del prompt, group
    if kv_bits < 16:
        raise ValueError("the latent store is served in full precision")
    low = precision == "fp8"
    eps = m["rms_norm_eps"]
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr = m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    inv, cs, scale = yarn(m)
    T = tokens.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(params["embed"]["table"])[tokens]

    def attention(x, p):
        a = p["attn"]
        h = _rmsnorm(x, p["norm1"]["scale"], eps)
        q = _mm(h, a["wq"]["w"], low).reshape(T, H, dn + dr)
        ckv = _mm(h, a["wkva"]["w"], low)
        c = _rmsnorm(ckv[:, :r], a["kva_norm"]["scale"], eps)
        k_pe = _rope(ckv[:, None, r:], inv, cs)                  # [T, 1, dr]
        kv = _mm(c, a["wkvb"]["w"], low).reshape(T, H, -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (T, H, dr))], -1)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv, cs)], -1)
        o = _attend(q, k, kv[..., dn:], scale, block, low)
        return x + _mm(o, a["wo"]["w"], low)

    def dense_layer(x, p):
        p = jax.tree.map(f32, p)
        x = attention(x, p)
        return x + _swiglu(_rmsnorm(x, p["norm2"]["scale"], eps), p["mlp"],
                           low), None

    def expert_layer(x, p):
        p = jax.tree.map(f32, p)
        x = attention(x, p)
        h = _rmsnorm(x, p["norm2"]["scale"], eps)
        return x + _experts(h, p["moe"], m, block, low), None

    if "lead" in params:
        x, _ = jax.lax.scan(dense_layer, x, params["lead"]["sub0"])
    x, _ = jax.lax.scan(expert_layer, x, params["blocks"]["sub0"])
    h = _rmsnorm(x[at], f32(params["final_norm"]["scale"]), eps)
    head = (f32(params["embed"]["table"]).T if m["tie_word_embeddings"]
            else f32(params["head"]["w"]))
    return _mm(h, head, low)
