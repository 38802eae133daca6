"""Dispatch wrappers: compiled Pallas on TPU, interpret-mode elsewhere.

`interpret=None` (the default) resolves from `jax.default_backend()` at
call time; pass an explicit bool to force either mode — tests use
`interpret=True` to run the compiled-path logic on CPU, and a future
non-TPU Pallas backend can pass `interpret=False` instead of being
silently mis-dispatched.
"""
from __future__ import annotations

from typing import Optional

from repro.kernels.blocking import resolve_interpret
from repro.kernels.decode_qattn import kernel, ref


def decode_attention_fused(q, k, k_scale, k_zero, v, v_scale, v_zero,
                           bias_main, rk, rv, bias_ring, *, bits: int,
                           group: int, block_s: int = 512,
                           return_mass: bool = False,
                           compute_dtype=None,
                           interpret: Optional[bool] = None, layer=None):
    """Fused [main store | residual ring] decode attention.

    See `kernel.decode_attn_pallas` for shapes (and `layer`, for a dense
    layer stack). Returns (out, mass|None)."""
    import jax.numpy as jnp
    return kernel.decode_attn_pallas(
        q, k, k_scale, k_zero, v, v_scale, v_zero, bias_main, rk, rv,
        bias_ring, bits=bits, group=group, block_s=block_s,
        return_mass=return_mass,
        compute_dtype=jnp.float32 if compute_dtype is None else compute_dtype,
        interpret=resolve_interpret(interpret), layer=layer)


def decode_attention_paged(q, block_tbl, pk, pk_scale, pk_zero, pv, pv_scale,
                           pv_zero, bias_main, rk, rv, bias_ring, *,
                           bits: int, group: int, return_mass: bool = False,
                           compute_dtype=None,
                           interpret: Optional[bool] = None):
    """Block-table decode attention over the shared pool.

    See `kernel.decode_attn_paged_pallas` for shapes; the caller passes
    the pool leaves of a `core.paging.PagedLayerKV` plus its (clamped)
    block table. Returns (out, mass|None)."""
    import jax.numpy as jnp
    return kernel.decode_attn_paged_pallas(
        q, block_tbl, pk, pk_scale, pk_zero, pv, pv_scale, pv_zero,
        bias_main, rk, rv, bias_ring, bits=bits, group=group,
        return_mass=return_mass,
        compute_dtype=jnp.float32 if compute_dtype is None else compute_dtype,
        interpret=resolve_interpret(interpret))


def decode_attention_quantized(q, kq, ks, kz, vq, vs, vz, bias, *,
                               bits: int, group: int, block_s: int = 512,
                               interpret: Optional[bool] = None):
    return kernel.decode_qattn_pallas(
        q, kq, ks, kz, vq, vs, vz, bias, bits=bits, group=group,
        block_s=block_s, interpret=resolve_interpret(interpret))


decode_attention_quantized_ref = ref.decode_qattn_ref
decode_attention_fused_ref = ref.decode_attn_ref
