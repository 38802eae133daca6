"""Mixture-of-Experts FFN with a top-k router.

Primary path: **capacity-based sort dispatch** (GShard/Switch style, the
production TPU formulation): tokens are sorted by expert id into an
[E, capacity, d_model] buffer, each expert runs one dense matmul, results
scatter back weighted by router probabilities. FLOPs are proportional to
*active* params (top_k), and under GSPMD the gather/scatter over the
token-sharded axis lowers to the MoE all-to-all.

`moe_apply_dense` is the soft-dispatch reference (exact, no token
dropping) used by small-E tests and as the oracle for the dispatch path.

`moe_apply_dropfree` is the serving path: a grouped matmul over the
token assignments sorted by expert (`kernels/moe_gmm`), so every token
is served by every expert it picked and its output depends on that
token alone — co-resident slots and prompt segmentation cannot change
it. Capacity stays a training-time notion.

Shared experts (DeepSeek) are one SwiGLU MLP of width `num_shared *
d_expert` under `p["shared"]`, added to every token's routed output.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.nn import layers as L

Array = jax.Array


class MoEStats(NamedTuple):
    """Routing counters of drop-free MoE layer calls, summed over calls
    (`repro.obs`'s `moe.*` counters read them at run end). They count
    the live rows' assignments: a decode step's rows of empty slots are
    computed but not counted."""
    assignments: Array   # int32: live token-expert assignments
    experts_hit: Array   # int32: experts given at least one of them
    calls: Array         # int32: MoE layer calls
    load_ratio: Array    # f32: busiest expert's assignments over the mean
    inplace_calls: Array  # int32: calls whose grouped matmul kernel read
                          # the layer-stacked expert weights in place

    @classmethod
    def zero(cls) -> "MoEStats":
        # one buffer a field: a donated cache may not hold one twice
        return cls(*(jnp.zeros((), jnp.int32) for _ in range(3)),
                   jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))


def add_stats(total, st):
    """Running counters plus one call's (None: a dense layer, or
    counters not kept)."""
    if total is None or st is None:
        return total
    return MoEStats(*(a + b for a, b in zip(total, st)))


class MoEAux(NamedTuple):
    load_balance_loss: Array   # scalar
    router_z_loss: Array       # scalar
    expert_load: Array         # [E] fraction of routed mass per expert
    drop_fraction: Array       # scalar — tokens dropped at capacity


def moe_init(key, d_model: int, d_expert: int, num_experts: int, dtype,
             num_shared: int = 0) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    e = num_experts
    init = L._init_dense
    p = {
        "router": init(kr, (d_model, e), d_model, jnp.float32),
        "gate": init(kg, (e, d_model, d_expert), d_model, dtype),
        "up": init(ku, (e, d_model, d_expert), d_model, dtype),
        "down": init(kd, (e, d_expert, d_model), d_expert, dtype),
    }
    if num_shared:
        p["shared"] = L.mlp_init(jax.random.fold_in(key, 4), d_model,
                                 num_shared * d_expert, bias=False,
                                 dtype=dtype)
    return p


def _route(p, x, top_k, norm_topk: bool = True):
    """Softmax router in float32, greedy top-k. The top-k weights are
    renormalized to sum to 1 where `norm_topk` (Mixtral), else kept as
    the softmax gave them (DeepSeek-V2)."""
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                 # [B, T, E]
    top_vals, top_idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True),
                                          1e-9)
    return logits, probs, top_vals, top_idx


def _shared(p, x, y):
    """y plus the shared experts' output, where the layer has them."""
    if "shared" not in p:
        return y
    return y + L.mlp(p["shared"], x)


def _aux(logits, probs, top_idx, top_vals, E, drop_frac):
    N = probs.shape[0] * probs.shape[1]
    load = jnp.zeros((E,), jnp.float32).at[top_idx.reshape(-1)].add(
        top_vals.reshape(-1)) / N
    importance = probs.mean(axis=(0, 1))
    lb = E * jnp.sum(load * importance)
    zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return MoEAux(lb, zl, load, drop_frac)


def moe_apply(
    p: dict, x: Array, *, top_k: int, capacity_factor: float = 1.25,
    norm_topk: bool = True,
) -> tuple[Array, MoEAux]:
    """Capacity-based sort dispatch. x: [B, T, d_model]."""
    from repro.nn import sharding as shd
    if shd.opt_enabled("weight_gather"):
        # keep experts sharded over tp when divisible (kimi 384e), else
        # tp stays on d_expert (mixtral 8e); either way the fsdp'd
        # d_model dim is gathered at use.
        E_ = p["gate"].shape[0]
        if shd.tp_divides(E_):
            spec_gu, spec_d = ("tp", None, None), ("tp", None, None)
        else:
            spec_gu, spec_d = (None, None, "tp"), (None, "tp", None)
        p = {**p,
             "gate": shd.constrain(p["gate"], *spec_gu),
             "up": shd.constrain(p["up"], *spec_gu),
             "down": shd.constrain(p["down"], *spec_d)}
    B, T, Dm = x.shape
    E = p["router"].shape[1]
    logits, probs, top_vals, top_idx = _route(p, x, top_k, norm_topk)

    N = B * T
    A = N * top_k                                   # assignments
    cap = max(-(-A * capacity_factor // E), 1)
    cap = int(min(cap, A))                          # never beyond drop-free
    x_flat = x.reshape(N, Dm)
    flat_e = top_idx.reshape(A)                     # token-major assignments
    flat_w = top_vals.reshape(A)

    order = jnp.argsort(flat_e, stable=True)        # [A]
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank = jnp.arange(A) - starts[sorted_e]
    keep = rank < cap
    rank_c = jnp.minimum(rank, cap - 1)
    tok = order // top_k

    xs = jnp.where(keep[:, None], x_flat[tok], 0).astype(x.dtype)
    buf = jnp.zeros((E, cap, Dm), x.dtype).at[sorted_e, rank_c].add(xs)
    if shd.opt_enabled("moe_ep_dispatch") and shd.tp_divides(E):
        # expert-parallel dispatch (§Perf): pin the expert buffer to the
        # tp axis so the scatter lowers to a token all-to-all instead of
        # gathering expert weights per token
        buf = shd.constrain(buf, "tp", None, None)

    g = jnp.einsum("ecd,edf->ecf", buf, p["gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["up"])
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u)
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["down"])  # [E, cap, Dm]

    y_sorted = y_buf[sorted_e, rank_c]              # [A, Dm]
    w_sorted = jnp.where(keep, flat_w[order], 0.0)
    out = jnp.zeros((N, Dm), jnp.float32).at[tok].add(
        y_sorted.astype(jnp.float32) * w_sorted[:, None])

    drop_frac = 1.0 - keep.mean()
    aux = _aux(logits, probs, top_idx, top_vals, E, drop_frac)
    y = out.reshape(B, T, Dm).astype(x.dtype)
    return _shared(p, x, y), aux


def moe_apply_dropfree(
    p: dict, x: Array, *, top_k: int, norm_topk: bool = True,
    use_kernel: bool = False, live: Array | None = None,
    block: Array | None = None,
) -> tuple[Array, MoEStats]:
    """Drop-free dispatch for serving. x: [B, T, d_model].

    The B*T*top_k assignments are sorted by expert and each expert's
    rows go through one grouped matmul (`kernels/moe_gmm`, the Pallas
    kernel where `use_kernel`); each token's top-k outputs are then
    gathered back in its own pick order and combined, so its result
    depends on that token alone.

    live: optional [B, T] bool, the rows that serve a request; the
    counters count only their assignments (all rows where None). What
    is computed does not depend on it.

    block: optional int32 scalar — `p`'s gate, up and down are the
    layer-stacked experts `[n_blocks * E, k, n]` (the serving loops hold
    them out of the layer scan, `nn.model._experts_out`), and this layer's
    are block `block`'s E; the kernel reads them where they lie
    (`kernels/moe_gmm`).

    Returns (y [B, T, d_model], this call's `MoEStats`)."""
    from repro.kernels.moe_gmm import ops as gmm_ops
    B, T, Dm = x.shape
    E = p["router"].shape[1]
    _, _, top_vals, top_idx = _route(p, x, top_k, norm_topk)
    N = B * T
    A = N * top_k
    flat_e = top_idx.reshape(A)
    order = jnp.argsort(flat_e, stable=True)          # [A] assignment ids
    sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    xs = x.reshape(N, Dm)[order // top_k]

    def mm(a, w):
        return gmm_ops.grouped_matmul(a, w, sizes, use_kernel=use_kernel,
                                      block=block)

    g = mm(xs, p["gate"])
    h = (jax.nn.silu(g).astype(x.dtype)
         * mm(xs, p["up"]).astype(x.dtype))
    y_sorted = mm(h, p["down"])                        # [A, Dm] f32
    y_tok = jnp.zeros_like(y_sorted).at[order].set(y_sorted)
    y = jnp.einsum("nk,nkd->nd", top_vals.reshape(N, top_k),
                   y_tok.reshape(N, top_k, Dm))
    y = y.reshape(B, T, Dm).astype(x.dtype)
    st = _live_stats(flat_e, live, E, top_k,
                     in_place=use_kernel and block is not None)
    return _shared(p, x, y), st


def _live_stats(flat_e, live, E, top_k, in_place: bool) -> MoEStats:
    """One call's counters over the live rows' assignments (flat_e:
    [N * top_k] token-major expert ids; live: [B, T] bool or None;
    in_place: the kernel read the stacked weights)."""
    w = (jnp.ones(flat_e.shape, jnp.int32) if live is None else
         jnp.repeat(live.reshape(-1).astype(jnp.int32), top_k))
    need = jnp.zeros((E,), jnp.int32).at[flat_e].add(w)
    n = jnp.sum(w)
    return MoEStats(n, jnp.sum(need > 0).astype(jnp.int32), jnp.int32(1),
                    jnp.max(need).astype(jnp.float32) * E
                    / jnp.maximum(n, 1).astype(jnp.float32),
                    jnp.int32(in_place))


def moe_apply_dense(p: dict, x: Array, *, top_k: int,
                    norm_topk: bool = True) -> tuple[Array, MoEAux]:
    """Soft-dispatch reference: every expert sees every token, masked by the
    combine weights. Exact (no drops); FLOPs ∝ E — tests/oracle only."""
    B, T, Dm = x.shape
    E = p["router"].shape[1]
    logits, probs, top_vals, top_idx = _route(p, x, top_k, norm_topk)
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, E, dtype=jnp.float32) * top_vals[..., None],
        axis=2)                                      # [B, T, E]
    xf = x.astype(jnp.float32)
    g = jnp.einsum("btd,edf->btef", xf, p["gate"].astype(jnp.float32))
    u = jnp.einsum("btd,edf->btef", xf, p["up"].astype(jnp.float32))
    h = jax.nn.silu(g) * u
    h = h * combine[..., None]
    y = jnp.einsum("btef,efd->btd", h, p["down"].astype(jnp.float32))
    aux = _aux(logits, probs, top_idx, top_vals, E, jnp.asarray(0.0))
    return _shared(p, x, y.astype(x.dtype)), aux
