"""Public jit'd wrappers: Pallas on TPU, interpret-mode on CPU, with the
ref implementation importable for oracles. The mode resolves from the
backend at call time (`blocking.resolve_interpret`)."""
from __future__ import annotations

from repro.kernels.blocking import resolve_interpret
from repro.kernels.kvquant import kernel, ref


def quantize_k(k, *, bits: int, group: int):
    return kernel.kquant_pallas(k, bits=bits, group=group,
                                interpret=resolve_interpret(None))


def quantize_v(v, *, bits: int, group: int):
    return kernel.vquant_pallas(v, bits=bits, group=group,
                                interpret=resolve_interpret(None))


unpack_dequant_k = ref.dequant_k_ref
unpack_dequant_v = ref.dequant_v_ref
