"""The work the algorithm needs, from shapes and lengths alone.

Rooflines and MFU divide these counts by measured time. They count what
the computation requires (valid compressed bytes, causal attention
pairs), never what a layout happens to move (transposed copies, padded
lanes, masked blocks), so a change that removes such traffic raises the
share instead of invalidating the count.

Positions follow the serving convention: a request with a `P`-token
prompt that is served `N` tokens runs its prompt through prefill (which
yields token 1) and then `N - 1` decode steps; the step that feeds the
token at position `p` attends over `p + 1` keys.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shape:
    """What the counts need of a configuration and its cache."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool = True
    kv_bits: int = 16      # main-store bits; 16 = dense bf16
    kv_group: int = 0      # quantization group = fp ring length (KIVI)

    @classmethod
    def of(cls, model: dict, engine: dict) -> "Shape":
        bits = 2 if engine["policy"] == "kivi2" else 16
        return cls(layers=model["num_layers"], d_model=model["d_model"],
                   heads=model["num_heads"], kv_heads=model["num_kv_heads"],
                   head_dim=model["head_dim"], d_ff=model["d_ff"],
                   vocab=model["vocab_size"],
                   tied=model["tie_embeddings"], kv_bits=bits,
                   kv_group=engine.get("window", 0) if bits < 16 else 0)

    def block_params(self) -> int:
        """Parameters of the layer stack (no embedding, no head)."""
        hq, hkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        per = (self.d_model * (hq + 2 * hkv) + hq * self.d_model
               + 3 * self.d_model * self.d_ff + 2 * self.d_model)
        return self.layers * per


# ---------------------------------------------------------------------------
# Attention


def causal_pairs(c0: int, c: int) -> int:
    """(query, key) pairs of `c` queries at positions c0..c0+c-1 under a
    causal mask over keys 0..c0+c-1."""
    return c * c0 + c * (c + 1) // 2


def attn_flops(pairs: int, s: Shape) -> int:
    """QK^T and PV over `pairs` (query, key) pairs, every layer."""
    return 4 * pairs * s.heads * s.head_dim * s.layers


def decode_kv_bytes(ctx: int, s: Shape) -> int:
    """Bytes one decode step must read of one slot's cache, all layers,
    with `ctx` keys valid. Quantized stores (KIVI): the rows before the
    last group boundary are 2-bit codes with per-channel K scale+zero per
    group and per-token V scale+zero (f32); the rest is the bf16 ring."""
    h, d = s.kv_heads, s.head_dim
    if s.kv_bits >= 16:
        per = 2 * ctx * h * d * 2
    else:
        g = s.kv_group
        q = (ctx - 1) // g * g          # rows flushed before this step
        r = ctx - q                     # ring rows, the new key included
        per = (2 * q * h * d * s.kv_bits // 8      # K and V codes
               + 2 * (q // g) * h * d * 4          # K scale + zero
               + 2 * q * h * 4                     # V scale + zero
               + 2 * r * h * d * 2)                # bf16 ring K and V
    return per * s.layers


def decode_qo_bytes(s: Shape) -> int:
    """Query read and output written by the decode kernel, all layers."""
    return 2 * s.heads * s.head_dim * 2 * s.layers


def chunk_io_bytes(c0: int, c: int, s: Shape) -> int:
    """Least bytes of one causal prefill chunk: its queries and outputs,
    and the bf16 keys and values up to its last position, all layers."""
    q_o = 2 * c * s.heads * s.head_dim * 2
    kv = 2 * (c0 + c) * s.kv_heads * s.head_dim * 2
    return (q_o + kv) * s.layers


# ---------------------------------------------------------------------------
# Per request


@dataclass(frozen=True)
class Served:
    """One finished request: prompt length and tokens served."""
    prompt: int
    served: int


def decode_positions(r: Served) -> range:
    """Positions fed by the request's decode steps."""
    return range(r.prompt, r.prompt + r.served - 1)


def prefill_chunks(prompt: int, chunk: int) -> list:
    """(c0, c) of each segment a chunked admission runs."""
    return [(c0, min(chunk, prompt - c0)) for c0 in range(0, prompt, chunk)]


def model_flops(reqs, s: Shape) -> int:
    """Forward FLOPs the served requests need: 2 x layer-stack params per
    forwarded token (prompt tokens and decode feeds), causal attention at
    each token's context, and the LM head once per served token. Nothing
    recomputed or padded counts."""
    tok = pairs = 0
    heads = 0
    for r in reqs:
        tok += r.prompt + r.served - 1
        pairs += causal_pairs(0, r.prompt)
        pairs += sum(p + 1 for p in decode_positions(r))
        heads += r.served
    return (2 * s.block_params() * tok + attn_flops(pairs, s)
            + 2 * s.d_model * s.vocab * heads)


def decode_attn_need(reqs, s: Shape) -> tuple:
    """(FLOPs, bytes) the decode attention kernel must do for the served
    requests' decode steps."""
    flops = byts = 0
    for r in reqs:
        for p in decode_positions(r):
            flops += attn_flops(p + 1, s)
            byts += decode_kv_bytes(p + 1, s) + decode_qo_bytes(s)
    return flops, byts


def prefill_attn_need(reqs, s: Shape, chunk: int) -> tuple:
    """(FLOPs, bytes) the flash prefill kernel must do for the served
    requests' prompts, streamed in `chunk`-token segments."""
    flops = byts = 0
    for r in reqs:
        for c0, c in prefill_chunks(r.prompt, chunk):
            flops += attn_flops(causal_pairs(c0, c), s)
            byts += chunk_io_bytes(c0, c, s)
    return flops, byts


def roofline_share(flops: int, byts: int, seconds: float,
                   peaks: dict) -> tuple:
    """(share in %, bound) of the least time the chip needs over the
    measured time; the bound names the larger of the two limits."""
    t_c = flops / peaks["bf16_flops"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
