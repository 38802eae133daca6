"""The fused decode path vs the materialize oracle.

Kernel-level parity is in tests/test_kernels.py; this file exercises the
*dispatch* layer: `nn.attention.decode_attention(use_kernels=True)` over
real `LayerKV` states (quantized + dense main stores, residual ring,
ragged lengths, GQA groups, sliding window), the attention-mass output
feeding `cache.accumulate_scores`, and end-to-end token equality of
`Engine.generate_continuous` with kernels on vs off.

Everything runs the compiled-path logic in interpret mode, so the suite
is TPU-free (the CI `kernels-interpret` job runs exactly these tests).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import cache as C
from repro.core.cache import CacheSpec
from repro.core.policy import presets
from repro.nn import attention as A
from repro.nn import model as M
from repro.serving import Engine, Request


def _layer_kv(spec, B, S_p, H, D, dtype, n_append=3, seed=0):
    """A lived-in cache: compressed prompt + a few decode appends (the
    appends put real tokens in the ring / trigger quantized flushes)."""
    ks = jax.random.split(jax.random.key(seed), 3 + 2 * n_append)
    k = jax.random.normal(ks[0], (B, S_p, H, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[1], (B, S_p, H, D), jnp.float32).astype(dtype)
    mass = jax.random.uniform(ks[2], (B, S_p))
    lc = C.compress_prompt(spec, k, v, mass, dtype=dtype)
    for t in range(n_append):
        kn = jax.random.normal(ks[3 + 2 * t], (B, H, D),
                               jnp.float32).astype(dtype)
        vn = jax.random.normal(ks[4 + 2 * t], (B, H, D),
                               jnp.float32).astype(dtype)
        lc = C.append_token(lc, spec, kn, vn)
    return lc


def _both_paths(q, lc, spec, dtype, window=0):
    o_ref, m_ref = A.decode_attention(q, lc, spec, window=window,
                                      dtype=dtype, use_kernels=False)
    o_ker, m_ker = A.decode_attention(q, lc, spec, window=window,
                                      dtype=dtype, use_kernels=True,
                                      interpret=True)
    return o_ref, m_ref, o_ker, m_ker


# fast representatives span the branch space (lowest-bit quant + ring at
# both GQA widths, dense with and without ring); the exhaustive
# bits × ring × gq cross product runs in the CI slow job
_FAST_KERNEL_CASES = {(2, True, 1), (2, True, 4), (16, False, 1),
                      (16, True, 4)}


@pytest.mark.parametrize("bits,ring,gq", [
    c if c in _FAST_KERNEL_CASES else pytest.param(*c,
                                                   marks=pytest.mark.slow)
    for c in [(b, r, g) for b in (2, 4, 8, 16) for r in (True, False)
              for g in (1, 4)]
], ids=lambda v: str(v))
def test_decode_attention_kernel_matches_materialize(bits, ring, gq):
    """Fused kernel == materialize oracle across bit widths, with and
    without the residual ring, ragged `length`/`rlen`, GQA group > 1."""
    if bits < 16 and not ring:
        pytest.skip("quantized cache requires the residual ring")
    B, H, D, W = 2, 2, 32, 8
    spec = CacheSpec(budget=32, window=W if ring else 0, bits=bits,
                     group=W if ring else 1, policy="h2o")
    dtype = jnp.float32
    lc = _layer_kv(spec, B, 48, H, D, dtype)
    # ragged rows: row 0 shorter in both the main store and the ring
    lc = lc._replace(length=lc.length.at[0].set(jnp.int32(16)))
    if ring:
        lc = lc._replace(rlen=jnp.minimum(
            lc.rlen, jnp.asarray([2, W], jnp.int32)))
    q = jax.random.normal(jax.random.key(7), (B, 1, H * gq, D), dtype)

    o_ref, m_ref, o_ker, m_ker = _both_paths(q, lc, spec, dtype)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(m_ker), np.asarray(m_ref),
                               atol=2e-5, rtol=2e-5)

    # the mass output drives identical H2O/NACL/Keyformer statistics
    s_ref = C.accumulate_scores(lc, spec, m_ref)
    s_ker = C.accumulate_scores(lc, spec, m_ker)
    np.testing.assert_allclose(np.asarray(s_ker.scores),
                               np.asarray(s_ref.scores), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_ker.r_scores),
                               np.asarray(s_ref.r_scores), atol=2e-5)


def test_decode_attention_kernel_bf16_cache():
    """bf16 model dtype: kernel tracks the oracle at bf16 rounding."""
    B, H, D, W = 1, 2, 64, 8
    spec = CacheSpec(budget=32, window=W, bits=2, group=W, policy="h2o")
    lc = _layer_kv(spec, B, 40, H, D, jnp.bfloat16)
    q = jax.random.normal(jax.random.key(3), (B, 1, H * 2, D), jnp.bfloat16)
    o_ref, m_ref, o_ker, m_ker = _both_paths(q, lc, spec, jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(o_ker, np.float32),
                               np.asarray(o_ref, np.float32), atol=3e-2)
    np.testing.assert_allclose(np.asarray(m_ker), np.asarray(m_ref),
                               atol=5e-3)


def test_decode_attention_kernel_skips_mass_when_untracked():
    """Policies that never read the mass statistic (streaming/quant-only)
    get the cheaper no-mass kernel: output parity still holds and the
    returned mass is a zeros placeholder accumulate_scores ignores."""
    B, H, D, W = 2, 2, 32, 8
    spec = CacheSpec(budget=32, window=W, bits=4, group=W,
                     policy="streaming")
    assert not spec.track_scores()
    lc = _layer_kv(spec, B, 48, H, D, jnp.float32)
    q = jax.random.normal(jax.random.key(11), (B, 1, H * 2, D), jnp.float32)
    o_ref, _, o_ker, m_ker = _both_paths(q, lc, spec, jnp.float32)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    assert m_ker.shape == (B, 32 + W)
    np.testing.assert_array_equal(np.asarray(m_ker), 0.0)
    after = C.accumulate_scores(lc, spec, m_ker)
    np.testing.assert_array_equal(np.asarray(after.scores),
                                  np.asarray(lc.scores))


def test_decode_attention_kernel_sliding_window():
    B, H, D, W = 2, 2, 32, 8
    spec = CacheSpec(budget=32, window=W, bits=4, group=W, policy="h2o")
    lc = _layer_kv(spec, B, 48, H, D, jnp.float32)
    q = jax.random.normal(jax.random.key(5), (B, 1, H * 2, D), jnp.float32)
    o_ref, m_ref, o_ker, m_ker = _both_paths(q, lc, spec, jnp.float32,
                                             window=24)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(m_ker), np.asarray(m_ref),
                               atol=2e-5)


def test_decode_attention_explicit_kernels_refuse_untileable_store():
    """A quantized store that is not a whole number of groups cannot be
    tiled by the fused kernel: the kernel path raises instead of quietly
    measuring the oracle."""
    B, H, D, W = 1, 2, 32, 8
    spec = CacheSpec(budget=0, window=W, bits=2, group=W, policy="streaming")
    lc = C.init_layer_kv(spec, B, 44, H, D, jnp.float32)
    assert lc.scores.shape[1] % spec.group
    q = jax.random.normal(jax.random.key(2), (B, 1, H, D), jnp.float32)
    with pytest.raises(ValueError, match="cannot tile"):
        A.decode_attention(q, lc, spec, dtype=jnp.float32, use_kernels=True,
                           interpret=True)


# ---------------------------------------------------------------------------
# End to end: generate_continuous, kernels on == kernels off
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def f32_model():
    # f32 weights so the only on/off differences are f32 roundoff (the
    # bf16 oracle rounds probabilities/scores through bf16 where the
    # kernel stays in f32 — token-exact equality needs a common dtype)
    cfg = reduced(get_config("paper-llama-7b"), num_layers=2)
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


@pytest.mark.parametrize("pname", [
    # kivi2 exercises the dequant-in-kernel path (the riskier branch);
    # the dense-store h2o e2e runs in the CI slow job
    pytest.param("h2o", marks=pytest.mark.slow),
    "kivi2",
])
def test_continuous_token_equality_kernels_on_off(f32_model, pname):
    """The fused decode path is a pure perf change: continuous batching
    emits identical tokens with kernels forced on (interpret mode on
    CPU) and forced off, across a selective (h2o) and a quantized
    (kivi2) policy, including an early-exit slot reuse."""
    cfg, params = f32_model
    L, NEW, n = 32, 6, 3
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab_size, size=(n, L)).astype(np.int32)
    pol = presets(budget=16, window=8)[pname]
    reqs = lambda: [Request(tokens=prompts[i], max_new=NEW)
                    for i in range(n)]

    off = Engine(cfg, params, pol, prompt_len=L, max_new=NEW, slots=2,
                 use_kernels=False).generate_continuous(reqs())
    on = Engine(cfg, params, pol, prompt_len=L, max_new=NEW, slots=2,
                use_kernels=True).generate_continuous(reqs())
    assert len(on.results) == len(off.results) == n
    for r_on, r_off in zip(on.results, off.results):
        np.testing.assert_array_equal(
            r_on.tokens, r_off.tokens,
            err_msg=f"{pname}: kernel path diverged (uid {r_on.uid})")


def test_train_forward_differentiable_with_kernels_on(f32_model):
    """Kernels are inference-only: pallas_call has no AD rule, so
    block_train must never dispatch them — value_and_grad over the
    training forward works with use_kernels forced on (regression)."""
    import dataclasses
    cfg, params = f32_model
    cfg = dataclasses.replace(cfg, use_kernels=True, remat="none")
    tokens = jnp.zeros((1, 16), jnp.int32)

    def loss(p):
        logits, _ = M.train_forward(p, cfg, {"tokens": tokens})
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    val, grads = jax.value_and_grad(loss)(params)
    assert jnp.isfinite(val)


def test_engine_use_kernels_flag_plumbs_to_config(f32_model):
    cfg, params = f32_model
    pol = presets(budget=16, window=8)["h2o"]
    eng = Engine(cfg, params, pol, prompt_len=32, max_new=4, slots=2,
                 use_kernels=True)
    assert eng.cfg.use_kernels is True
    eng = Engine(cfg, params, pol, prompt_len=32, max_new=4, slots=2)
    assert eng.cfg.use_kernels is None


# ---------------------------------------------------------------------------
# In-place decode of a dense store
# ---------------------------------------------------------------------------


def _scan_decode_step(params, cfg, cache, token, spec, key, append_mask):
    """The layer loop that passes each layer's piece through the scan:
    sliced out of the stacked store, appended and attended layer by
    layer, and restacked (uniform attention archs; the keys split as
    `M.decode_step` splits them)."""
    from repro.nn import blocks as Bk
    from repro.nn import layers as Ly
    x = Ly.embed(params["embed"], token)
    n_sb = cfg.num_layers
    keys = jax.random.split(key, n_sb).reshape(n_sb, 1)

    def body(x, xs):
        p_sb, a_sl, ks = xs
        piece = jax.tree.map(lambda t: t[0], a_sl)
        x, piece, _ = Bk.block_decode(p_sb["sub0"], x, cfg, "attn", spec,
                                      piece, key=ks[0],
                                      append_mask=append_mask)
        return x, jax.tree.map(lambda t: t[None], piece)

    x, attn_c = jax.lax.scan(body, x, (params["blocks"], cache.attn, keys))
    return M._logits(params, cfg, x)[:, 0], cache._replace(attn=attn_c)


@pytest.fixture(scope="module")
def aligned_model():
    """f32 weights, 2 KV heads of 128: a dense store the kernel can read
    where it lies (`blocking.reads_in_place`)."""
    cfg = reduced(get_config("paper-llama-7b"), num_layers=2, head_dim=128)
    return cfg, M.init_params(jax.random.key(0), cfg)


@pytest.mark.parametrize("pname", ["full", "h2o"])
def test_inplace_decode_step_matches_layer_scan(aligned_model, pname):
    """Kernels on (interpret mode): the in-place `decode_step` over a
    dense store gives the same logits, tokens and cache as the layer
    scan, step after step, with ragged slots (an `append_mask` holds
    rows back) and, for h2o, evictions once its 16-row store fills."""
    import dataclasses
    cfg, params = aligned_model
    cfg = dataclasses.replace(cfg, use_kernels=True)
    spec = presets(budget=16, window=8)[pname].spec
    assert M.decode_in_place(M.init_cache(cfg, spec, 1, 8), spec)
    B, steps = 3, 22
    cache = M.init_cache(cfg, spec, B, 24)
    # kvlint: ok(jit-donate: a CPU parity test, where donation is a no-op)
    inplace = jax.jit(lambda c, t, k, m: M.decode_step(
        params, cfg, c, t, spec, key=k, append_mask=m))
    # kvlint: ok(jit-donate: a CPU parity test, where donation is a no-op)
    scan = jax.jit(lambda c, t, k, m: _scan_decode_step(
        params, cfg, c, t, spec, k, m))
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    c_in = c_sc = cache
    for t in range(steps):
        mask = jnp.asarray([True, t % 3 != 1, t % 2 == 0])
        key = jax.random.key(t)
        lg_in, c_in = inplace(c_in, tok, key, mask)
        lg_sc, c_sc = scan(c_sc, tok, key, mask)
        np.testing.assert_array_equal(np.asarray(lg_in), np.asarray(lg_sc),
                                      err_msg=f"{pname} step {t}")
        tok = jnp.argmax(lg_in, -1).astype(jnp.int32)[:, None]
    for name, a, b in zip(C.LayerKV._fields, c_in.attn, c_sc.attn):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{pname} cache.{name}")
    lengths = np.asarray(c_in.attn.length)[0, 0]
    assert len(set(lengths.tolist())) > 1, lengths      # ragged slots


@pytest.mark.parametrize("pname,paged,aligned,in_place", [
    ("full", False, True, True), ("h2o", False, True, True),
    ("kivi2", False, True, False), ("full", True, True, False),
    ("full", False, False, False),
])
def test_engine_counts_inplace_decode_steps(f32_model, aligned_model, pname,
                                            paged, aligned, in_place):
    """`decode.inplace_steps` counts every decode step of an engine over
    a dense store of whole 128-lane heads, and none over a quantized, a
    paged, or a dense store of 64-wide heads."""
    from repro.obs import Metrics
    cfg, params = aligned_model if aligned else f32_model
    kw = dict(prompt_len=32, max_new=4, slots=2, buckets=(32,))
    if paged:
        kw.update(paged=True, block_len=8)
    mx = Metrics()
    rng = np.random.default_rng(2)
    reqs = [Request(tokens=rng.integers(0, cfg.vocab_size, 32).astype(
        np.int32), max_new=4) for _ in range(3)]
    res = Engine(cfg, params, presets(budget=16, window=8)[pname],
                 metrics=mx, **kw).generate_continuous(reqs)
    assert res.decode_steps > 0
    assert res.inplace_steps == (res.decode_steps if in_place else 0)
    assert mx.snapshot()["decode.inplace_steps"] == res.inplace_steps
