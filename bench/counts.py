"""The work the algorithm needs, from shapes and lengths alone.

Rooflines and MFU divide these counts by measured time. They count what
the computation requires (valid compressed bytes, causal attention
pairs), never what a layout happens to move (transposed copies, padded
lanes, masked blocks), so a change that removes such traffic raises the
share instead of invalidating the count.

What one token costs depends on the architecture, and comes from its
work object (`archs/<name>.py`, `work(conf, engine)`), whose methods
`block_params`, `attn_flops`, `head_flops`, `decode_kv_bytes`,
`decode_qo_bytes` and `chunk_io_bytes` say what each counts. This module
sums them over the served requests, and keeps what no architecture
changes: how the cell's policy stores a cached row (`Store`), causal
pairs, and the positions a request's prompt and decode steps run at.

Positions follow the serving convention: a request with a `P`-token
prompt that is served `N` tokens runs its prompt through prefill (which
yields token 1) and then `N - 1` decode steps; the step that feeds the
token at position `p` attends over `p + 1` keys.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Store:
    """How the cell's policy keeps cached keys and values: the policy's
    decision, not the architecture's (`check._kv` maps the same policies
    for the reference)."""
    bits: int = 16         # main-store bits; 16 = dense bf16
    group: int = 0         # quantization group = fp ring length (KIVI)

    @classmethod
    def of(cls, engine: dict) -> "Store":
        if engine["policy"] == "kivi2":
            return cls(bits=2, group=engine.get("window", 0))
        return cls()

    def kv_bytes(self, ctx: int, vectors: int, width: int) -> int:
        """Bytes one decode step must read of one slot's keys and values
        in one layer, with `ctx` keys valid, where each position keeps
        `vectors` key and as many value vectors of `width` values. Dense:
        bf16 rows. Quantized (KIVI): the rows before the last group
        boundary are `bits`-bit codes with per-channel K scale+zero per
        group and per-vector V scale+zero per token (f32); the rest is
        the bf16 ring."""
        row = vectors * width
        if self.bits >= 16:
            return 2 * ctx * row * 2
        g = self.group
        q = (ctx - 1) // g * g          # rows flushed before this step
        r = ctx - q                     # ring rows, the new key included
        return (2 * q * row * self.bits // 8       # K and V codes
                + 2 * (q // g) * row * 4           # K scale + zero
                + 2 * q * vectors * 4              # V scale + zero
                + 2 * r * row * 2)                 # bf16 ring K and V


def causal_pairs(c0: int, c: int) -> int:
    """(query, key) pairs of `c` queries at positions c0..c0+c-1 under a
    causal mask over keys 0..c0+c-1."""
    return c * c0 + c * (c + 1) // 2


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


def model_flops(reqs, w) -> int:
    """Forward FLOPs the served requests need, by work object `w`: 2 x
    the layer-stack parameters a token multiplies by, per forwarded token
    (prompt tokens and decode feeds), causal attention at each token's
    context, and the LM head once per served token. Nothing recomputed or
    padded counts."""
    tok = pairs = served = 0
    for r in reqs:
        tok += r.prompt + r.served - 1
        pairs += causal_pairs(0, r.prompt)
        pairs += sum(p + 1 for p in decode_positions(r))
        served += r.served
    return (2 * w.block_params() * tok + w.attn_flops(pairs)
            + w.head_flops() * served)


def decode_attn_need(reqs, w) -> tuple:
    """(FLOPs, bytes) the decode attention kernel must do for the served
    requests' decode steps, by work object `w`."""
    flops = byts = 0
    for r in reqs:
        for p in decode_positions(r):
            flops += w.attn_flops(p + 1)
            byts += w.decode_kv_bytes(p + 1) + w.decode_qo_bytes()
    return flops, byts


def prefill_attn_need(reqs, w, chunk: int) -> tuple:
    """(FLOPs, bytes) the flash prefill kernel must do for the served
    requests' prompts, streamed in `chunk`-token segments, by work object
    `w`."""
    flops = byts = 0
    for r in reqs:
        for c0, c in prefill_chunks(r.prompt, chunk):
            flops += w.attn_flops(causal_pairs(c0, c))
            byts += w.chunk_io_bytes(c0, c)
    return flops, byts


def roofline_share(flops: int, byts: int, seconds: float,
                   peaks: dict) -> tuple:
    """(share in %, bound) of the least time the chip needs over the
    measured time; the bound names the larger of the two limits."""
    t_c = flops / peaks["bf16_flops"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
