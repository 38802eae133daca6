"""Grouped matmul for drop-free routed experts: `lhs[rows of group g] @
rhs[g]` for every expert g, rows sorted by expert, so a row's result
depends on that row alone.

On the chip this is the megablox `gmm` Pallas kernel of JAX (each
expert's weights are read once per row tile its rows fall in, empty
experts not at all), called unjitted inside the jitted wrapper
`moe_gmm`, whose name the kernel then carries in the profiler's "XLA
Ops" line. `jax.lax.ragged_dot` is its oracle (`grouped_matmul_ref`).

With `block`, rhs is the layer stack of the experts' weights, `[n_blocks
* G, k, n]`, and the call reads block `block`'s G experts where they lie:
the kernel takes the whole stack, with the call's group sizes placed at
`[block * G, (block + 1) * G)` among zeros (empty groups take no grid
steps, and each row tile's weights are fetched by its group's index in
the stack), so no layer's weights are sliced out and copied before it.
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
def moe_gmm(lhs, rhs, group_sizes, block=None, *, interpret: bool = False):
    """lhs [m, k] rows sorted by group; rhs [G, k, n], or with `block`
    (int32 scalar) the stack [n_blocks * G, k, n]; group_sizes [G] int32
    summing to m. Returns [m, n] float32."""
    # the module, not the package's custom-vjp wrapper of the same name;
    # unjitted, so that the kernel is named after this wrapper
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__
    if block is not None:
        # not gmm's `group_offset`, which expects rhs to hold the call's
        # groups alone
        group_sizes = jax.lax.dynamic_update_slice_in_dim(
            jnp.zeros((rhs.shape[0],), jnp.int32), group_sizes,
            block * group_sizes.shape[0], axis=0)
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
                   block=None, interpret: Optional[bool] = None):
    """[m, n] float32: the Pallas kernel where `use_kernel`, else the
    oracle. With `block`, rhs is the stack [n_blocks * G, k, n] and the
    call reads block `block`'s G groups (the kernel in place; the oracle
    slices them out)."""
    if use_kernel:
        return moe_gmm(lhs, rhs, group_sizes, block,
                       interpret=resolve_interpret(interpret))
    if block is not None:
        G = group_sizes.shape[0]
        rhs = jax.lax.dynamic_slice_in_dim(rhs, block * G, G)
    return grouped_matmul_ref(lhs, rhs, group_sizes)


def grouped_matmul_ref(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)
