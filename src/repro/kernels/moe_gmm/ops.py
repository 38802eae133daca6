"""Grouped matmul for drop-free routed experts: `lhs[rows of group g] @
rhs[g]` for every expert g, rows sorted by expert, so a row's result
depends on that row alone.

On the chip this is the megablox `gmm` Pallas kernel of JAX (each
expert's weights are read once per row tile its rows fall in, empty
experts not at all), called unjitted inside the jitted wrapper
`moe_gmm`, whose name the kernel then carries in the profiler's "XLA
Ops" line. `jax.lax.ragged_dot` is its oracle (`grouped_matmul_ref`).
"""
from __future__ import annotations

import functools
import importlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.blocking import resolve_interpret


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn): row tiles of at most 128 (decode batches fit one or
    two), and weight tiles of at most 1536 x 1536 lanes (3 MiB of bf16,
    double-buffered within the default scoped VMEM); a dim that small
    is taken whole."""
    tm = min(128, -(-m // 16) * 16)
    tk = k if k <= 1536 else 512
    tn = n if n <= 1536 else 512
    return tm, tk, tn


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(lhs, rhs, group_sizes, *, interpret: bool = False):
    """lhs [m, k] rows sorted by group; rhs [G, k, n]; group_sizes [G]
    int32 summing to m. Returns [m, n] float32."""
    # the module, not the package's custom-vjp wrapper of the same name;
    # unjitted, so that the kernel is named after this wrapper
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__
    m, k = lhs.shape
    tm, tk, tn = _tiling(m, k, rhs.shape[2])
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
              tiling=(tm, tk, tn), interpret=interpret)
    # rows past the groups' sum are never written: drop the padding
    return out[:m]


def grouped_matmul(lhs, rhs, group_sizes, *, use_kernel: bool,
                   interpret: Optional[bool] = None):
    """[m, n] float32: the Pallas kernel where `use_kernel`, else the
    oracle."""
    if use_kernel:
        return moe_gmm(lhs, rhs, group_sizes,
                       interpret=resolve_interpret(interpret))
    return grouped_matmul_ref(lhs, rhs, group_sizes)


def grouped_matmul_ref(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)
