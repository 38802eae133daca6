"""Expert-parallel MoE via shard_map — the §Perf lever for MoE decode.

The GSPMD-sharded dispatch (nn/moe.py) lets XLA pick the collectives and
it chooses a per-assignment `[N·top_k, d_model]` all-reduce for the
combine (EXPERIMENTS.md §Perf pair 4). This module states the intent
explicitly: experts live on the tp axis ("model"), activations are
replicated across it (they are already batch-sharded over "data"), each
shard computes ONLY its local experts' assignments, and the combine is a
single psum of the token-sized partial outputs — `[N, d_model]` bytes
instead of `[N·top_k, d_model]`-sized gathers, and FLOPs split 1/ep per
shard.

Correctness contract: identical to `moe_apply_dense` when capacity is
drop-free (tests/test_moe_ep.py validates on 8 host devices).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Array = jax.Array


def _local_moe_kernel(router, gate, up, down, x, *, top_k: int,
                      capacity_factor: float, ep_axis: str, n_experts: int):
    """Runs per ep-shard. gate/up/down: [E_loc, ...]; x: [N, Dm]
    (replicated over ep). Returns this shard's partial y [N, Dm]."""
    E_loc = gate.shape[0]
    shard = jax.lax.axis_index(ep_axis)
    e_lo = shard * E_loc

    logits = x.astype(jnp.float32) @ router              # [N, E] (global)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)

    N = x.shape[0]
    A = N * top_k
    cap = max(int(-(-A * capacity_factor // n_experts)), 1)
    cap = min(cap * E_loc, A)            # local buffer across E_loc experts

    flat_e = top_idx.reshape(A)
    flat_w = top_vals.reshape(A)
    local = (flat_e >= e_lo) & (flat_e < e_lo + E_loc)   # my assignments
    # rank within local set (stable order), capacity-capped
    lrank = jnp.cumsum(local.astype(jnp.int32)) - 1
    keep = local & (lrank < cap)
    slot = jnp.where(keep, lrank, cap - 1)

    tok = jnp.arange(A) // top_k
    xs = jnp.where(keep[:, None], x[tok], 0).astype(x.dtype)
    buf = jnp.zeros((cap, x.shape[1]), x.dtype).at[slot].add(
        jnp.where(keep[:, None], xs, 0))
    eid = jnp.zeros((cap,), jnp.int32).at[slot].max(
        jnp.where(keep, flat_e - e_lo, 0))

    wg = gate[eid]                                        # [cap, Dm, F]
    wu = up[eid]
    wd = down[eid]                                        # [cap, F, Dm]
    g = jnp.einsum("cd,cdf->cf", buf, wg)
    u = jnp.einsum("cd,cdf->cf", buf, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    yb = jnp.einsum("cf,cfd->cd", h, wd)                  # [cap, Dm]

    y_sorted = yb[slot] * jnp.where(keep, flat_w, 0.0)[:, None]
    y = jnp.zeros((N, x.shape[1]), jnp.float32).at[tok].add(
        y_sorted.astype(jnp.float32))
    return jax.lax.psum(y, ep_axis)                       # combine


def moe_apply_expert_parallel(
    p: dict, x: Array, *, top_k: int, mesh: Mesh,
    capacity_factor: float = 1.25, ep_axis: str = "model",
    dp_spec: P = P(),
) -> Array:
    """x: [B, T, Dm] (replicated over `ep_axis`; optionally sharded over
    other axes per dp_spec). p: moe params with experts divisible by the
    ep axis. Returns y: [B, T, Dm]."""
    B, T, Dm = x.shape
    E = p["router"].shape[1]
    ep = mesh.shape[ep_axis]
    assert E % ep == 0, (E, ep)

    fn = functools.partial(_local_moe_kernel, top_k=top_k,
                           capacity_factor=capacity_factor,
                           ep_axis=ep_axis, n_experts=E)
    expert_spec = P(ep_axis)     # shard dim 0 (experts)
    smapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(), expert_spec, expert_spec, expert_spec, dp_spec),
        out_specs=dp_spec,
        check_vma=False,
    )
    x2 = x.reshape(B * T, Dm)
    y = smapped(p["router"], p["gate"], p["up"], p["down"], x2)
    return y.reshape(B, T, Dm).astype(x.dtype)
