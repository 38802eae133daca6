"""Dispatch wrapper: compiled Pallas on TPU, interpret-mode elsewhere.

`interpret=None` resolves from the backend at call time; pass a bool to
force either mode (tests force `interpret=True` on CPU)."""
from __future__ import annotations

from typing import Optional

from repro.kernels.blocking import pad_rows, resolve_interpret, tile
from repro.kernels.flash_prefill import kernel, ref


def flash_attention(q, k, v, *, window: int = 0, bq: int = 512,
                    bk: int = 512, interpret: Optional[bool] = None):
    """q: [B, T, Hq, D]; k, v: [B, T, Hkv, D]. Causal (optionally sliding
    window) flash attention. One sublane-aligned block size serves both
    axes (`blocking.tile`); padded rows sit after every real position, so
    the causal mask hides them and their outputs are sliced off."""
    interpret = resolve_interpret(interpret)
    T = q.shape[1]
    b, Tp = tile(T, 8, min(bq, bk))
    out = kernel.flash_prefill_pallas(
        pad_rows(q, Tp - T), pad_rows(k, Tp - T), pad_rows(v, Tp - T),
        window=window, bq=b, bk=b, interpret=interpret)
    return out[:, :T]


def flash_attention_chunk(q, k, v, *, q_offset, window: int = 0,
                          bq: int = 512, bk: int = 512,
                          interpret: Optional[bool] = None):
    """Chunked-prefill variant: q is one prompt segment [B, C, Hq, D]
    rotated at absolute positions q_offset..q_offset+C; k, v are the
    full prompt scratch [B, T, Hkv, D] (rows beyond the segment end
    still zero — masked by the absolute-position causal test, as are
    the pad rows after T). q_offset is a traced scalar: one compile per
    segment length."""
    interpret = resolve_interpret(interpret)
    C, T = q.shape[1], k.shape[1]
    bq, Cp = tile(C, 8, bq)
    bk, Tp = tile(T, 8, bk)
    out = kernel.flash_prefill_chunk_pallas(
        pad_rows(q, Cp - C), pad_rows(k, Tp - T), pad_rows(v, Tp - T),
        q_offset, window=window, bq=bq, bk=bk, interpret=interpret)
    return out[:, :C]


def flash_verify(q, k, v, kv_pos, bias, q_pos, *, window: int = 0,
                 bk: int = 512, interpret: Optional[bool] = None):
    """Speculative-verify attention: q is one speculated segment
    [B, L, Hq, D] (already appended to the cache), k/v the materialized
    cache view [B, Tk, Hkv, D] with explicit absolute positions `kv_pos`
    [B, Tk] and additive validity `bias` [B, Tk]; q_pos [B, L]. The
    segment is padded up to a sublane multiple with an impossible query
    position (every key masked; padded rows are sliced off), and the key
    axis up to its tile with masked keys."""
    interpret = resolve_interpret(interpret)
    L, Tk = q.shape[1], k.shape[1]
    pad = (-L) % 8
    q = pad_rows(q, pad)
    q_pos = pad_rows(q_pos, pad, -(2 ** 30))
    bk, Tp = tile(Tk, 8, bk)
    kpad = Tp - Tk
    out = kernel.flash_verify_pallas(
        q, pad_rows(k, kpad), pad_rows(v, kpad),
        pad_rows(kv_pos, kpad, 2 ** 30), pad_rows(bias, kpad, -1e30),
        q_pos, window=window, bk=bk, interpret=interpret)
    return out[:, :L]


flash_attention_ref = ref.flash_prefill_ref
flash_verify_ref = ref.flash_verify_ref
