"""Dispatch wrapper: compiled Pallas on TPU, interpret mode elsewhere
(`blocking.resolve_interpret`)."""
from __future__ import annotations

from typing import Optional

from repro.kernels.blocking import resolve_interpret
from repro.kernels.decode_mla import kernel, ref


def decode_mla(q, store, n_valid, *, layer=None, kv_lora: int,
               scale: float, block_s: int = 1024,
               interpret: Optional[bool] = None):
    """Absorbed MLA decode attention; see `kernel.decode_mla_pallas`."""
    return kernel.decode_mla_pallas(
        q, store, n_valid, layer, kv_lora=kv_lora, scale=float(scale),
        block_s=block_s, interpret=resolve_interpret(interpret))


decode_mla_ref = ref.decode_mla_ref
