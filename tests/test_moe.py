"""MoE dispatch: capacity-based sort dispatch == dense soft dispatch when
drop-free; capacity drops are counted; load stats sane."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import moe as MoE


def _params(key, Dm=32, F=64, E=4):
    return MoE.moe_init(key, Dm, F, E, jnp.float32)


def test_capacity_matches_dense_when_dropfree():
    p = _params(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    y_dense, aux_d = MoE.moe_apply_dense(p, x, top_k=2)
    y_cap, aux_c = MoE.moe_apply(p, x, top_k=2, capacity_factor=4.0)
    assert float(aux_c.drop_fraction) == 0.0
    np.testing.assert_allclose(np.asarray(y_cap), np.asarray(y_dense),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(aux_c.expert_load),
                               np.asarray(aux_d.expert_load), atol=1e-6)


def test_capacity_drops_under_tight_factor():
    p = _params(jax.random.key(2))
    # force imbalance: all tokens identical -> same experts chosen
    x = jnp.ones((1, 32, 32))
    y, aux = MoE.moe_apply(p, x, top_k=2, capacity_factor=0.5)
    assert float(aux.drop_fraction) > 0.0
    assert bool(jnp.all(jnp.isfinite(y)))


def test_load_balance_loss_minimized_at_uniform():
    E = 8
    load = jnp.full((E,), 1.0 / E)
    imp = jnp.full((E,), 1.0 / E)
    lb_uniform = E * jnp.sum(load * imp)
    skew = jnp.zeros((E,)).at[0].set(1.0)
    lb_skew = E * jnp.sum(skew * skew)
    assert float(lb_uniform) == pytest.approx(1.0)
    assert float(lb_skew) > float(lb_uniform)


def test_grad_flows_through_dispatch():
    p = _params(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (1, 8, 32))

    def loss(p):
        y, _ = MoE.moe_apply(p, x, top_k=2, capacity_factor=4.0)
        return jnp.sum(y ** 2)

    g = jax.grad(loss)(p)
    gn = sum(float(jnp.abs(v).sum()) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("use_kernel", [False, True])
def test_dropfree_matches_dense_and_counts(use_kernel):
    """The serving dispatch (tokens sorted by expert, one grouped matmul;
    the megablox kernel in interpret mode where `use_kernel`) equals the
    soft-dispatch reference, routed weights not renormalized,
    shared expert included; its counters count what it computed."""
    p = MoE.moe_init(jax.random.key(5), 32, 64, 4, jnp.float32,
                     num_shared=1)
    x = jax.random.normal(jax.random.key(6), (3, 5, 32))
    kw = dict(top_k=2, norm_topk=False)
    y, st = MoE.moe_apply_dropfree(p, x, use_kernel=use_kernel, **kw)
    y_dense, _ = MoE.moe_apply_dense(p, x, **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               atol=1e-4, rtol=1e-4)
    _, _, _, idx = MoE._route(p, x, 2)
    load = np.bincount(np.asarray(idx).ravel(), minlength=4)
    assert int(st.assignments) == 30 and int(st.calls) == 1
    assert int(st.experts_hit) == int((load > 0).sum())
    assert float(st.load_ratio) == pytest.approx(load.max() / (30 / 4))


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("block", [0, 1, 2])
def test_grouped_matmul_reads_its_block_of_the_stack(use_kernel, block):
    """The grouped matmul over a stack of 3 blocks' experts, `[3 * E, k,
    n]`, at block `block` equals the call on that block's own `[E, k, n]`
    slice, with one expert given no rows and the rows not a whole row
    tile; bit for bit, with the kernel (interpret mode, whose only change
    is which weights a tile reads) and with the oracle."""
    from repro.kernels.moe_gmm import ops as gmm_ops
    E, k, n = 4, 32, 48
    stack = jax.random.normal(jax.random.key(13), (3 * E, k, n))
    sizes = jnp.array([5, 0, 6, 2], jnp.int32)
    lhs = jax.random.normal(jax.random.key(14), (13, k))
    got = gmm_ops.grouped_matmul(lhs, stack, sizes, use_kernel=use_kernel,
                                 block=jnp.int32(block), interpret=True)
    want = gmm_ops.grouped_matmul(lhs, stack[block * E:(block + 1) * E],
                                  sizes, use_kernel=use_kernel,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the oracle on the slice agrees with it
    ref = gmm_ops.grouped_matmul_ref(lhs, stack[block * E:(block + 1) * E],
                                     sizes)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-4, rtol=1e-5)


def test_dropfree_counts_live_rows_only():
    """Rows marked not live (a decode step's empty slots) are computed
    as before but left out of the counters."""
    p = MoE.moe_init(jax.random.key(11), 32, 64, 8, jnp.float32)
    x = jax.random.normal(jax.random.key(12), (4, 1, 32))
    live = jnp.array([True, False, True, False])[:, None]
    y_all, st_all = MoE.moe_apply_dropfree(p, x, top_k=2)
    y, st = MoE.moe_apply_dropfree(p, x, top_k=2, live=live)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_all))
    _, _, _, idx = MoE._route(p, x, 2)
    load = np.bincount(np.asarray(idx)[[0, 2]].ravel(), minlength=8)
    assert int(st_all.assignments) == 8 and int(st.assignments) == 4
    assert int(st.experts_hit) == int((load > 0).sum())
    assert float(st.load_ratio) == pytest.approx(load.max() / (4 / 8))
    _, st0 = MoE.moe_apply_dropfree(p, x, top_k=2, live=live & False)
    assert int(st0.assignments) == int(st0.experts_hit) == 0


def test_dropfree_token_ignores_its_batch():
    """A token's output depends on that token alone: bit for bit the same
    served alone or beside others that route elsewhere."""
    p = MoE.moe_init(jax.random.key(7), 32, 64, 8, jnp.float32)
    x = jax.random.normal(jax.random.key(8), (4, 6, 32))
    y, _ = MoE.moe_apply_dropfree(p, x, top_k=2)
    y1, _ = MoE.moe_apply_dropfree(p, x[1:2, 2:3], top_k=2)
    np.testing.assert_array_equal(np.asarray(y[1, 2]), np.asarray(y1[0, 0]))


def test_route_renormalization_is_a_choice():
    p = MoE.moe_init(jax.random.key(9), 16, 8, 4, jnp.float32)
    x = jax.random.normal(jax.random.key(10), (1, 3, 16))
    _, probs, w, idx = MoE._route(p, x, 2)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    _, _, w_raw, _ = MoE._route(p, x, 2, norm_topk=False)
    np.testing.assert_allclose(
        np.asarray(w_raw),
        np.take_along_axis(np.asarray(probs), np.asarray(idx), -1),
        rtol=1e-6)


def test_zero_stats_hold_a_buffer_each():
    """The counters ride in a cache the engine donates; XLA refuses a
    buffer donated twice, so no two fields may share one."""
    st = MoE.MoEStats.zero()
    ptrs = {x.unsafe_buffer_pointer() for x in st}
    assert len(ptrs) == len(st) == 5
