"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp ref oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.kvquant import kernel as kq_kernel, ref as kq_ref
from repro.kernels.decode_qattn import kernel as dq_kernel, ref as dq_ref
from repro.kernels.flash_prefill import kernel as fp_kernel, ref as fp_ref


# ---------------------------------------------------------------------------
# kvquant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,D,G", [(1, 64, 2, 32, 16), (2, 128, 4, 64, 32),
                                       (1, 32, 1, 128, 32)])
def test_kquant_matches_ref(bits, dtype, B, S, H, D, G):
    k = (jax.random.normal(jax.random.key(0), (B, S, H, D), jnp.float32)
         * 2.0).astype(dtype)
    pk, sk, zk = kq_kernel.kquant_pallas(k, bits=bits, group=G,
                                         interpret=True)
    pk2, sk2, zk2 = kq_ref.kquant_ref(k, bits, G)
    # codes may differ by 1 level on rounding ties: compare dequantized
    d1 = kq_ref.dequant_k_ref(pk, sk, zk, bits, G, jnp.float32)
    d2 = kq_ref.dequant_k_ref(pk2, sk2, zk2, bits, G, jnp.float32)
    tol = float(jnp.max(sk)) + 1e-6
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=tol)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sk2), rtol=1e-5)
    # round-trip error bound
    err = float(jnp.max(jnp.abs(d1 - k.astype(jnp.float32))))
    assert err <= float(jnp.max(sk)) / 2 + 1e-2


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("B,S,H,D,G", [(2, 64, 2, 32, 16), (1, 128, 8, 64, 64)])
def test_vquant_matches_ref(bits, B, S, H, D, G):
    v = jax.random.normal(jax.random.key(1), (B, S, H, D), jnp.float32) * 3
    pv, sv, zv = kq_kernel.vquant_pallas(v, bits=bits, group=G,
                                         interpret=True)
    pv2, sv2, zv2 = kq_ref.vquant_ref(v, bits)
    d1 = kq_ref.dequant_v_ref(pv, sv, zv, bits, jnp.float32)
    d2 = kq_ref.dequant_v_ref(pv2, sv2, zv2, bits, jnp.float32)
    tol = float(jnp.max(sv)) + 1e-6
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=tol)


def test_pack_unpack_roundtrip():
    for bits in (2, 4, 8):
        q = jax.random.randint(jax.random.key(2), (3, 16), 0, 1 << bits)
        p = kq_ref.pack_ref(q, bits)
        assert p.shape[-1] == 16 * bits // 8
        u = kq_ref.unpack_ref(p, bits, 16)
        np.testing.assert_array_equal(np.asarray(u), np.asarray(q))


# ---------------------------------------------------------------------------
# decode_qattn
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("B,S,Hkv,Gq,D,G,BS", [
    (2, 256, 2, 4, 64, 32, 64),
    (1, 128, 1, 8, 128, 32, 32),
    (1, 512, 4, 1, 64, 64, 128),
])
def test_decode_qattn_matches_ref(bits, B, S, Hkv, Gq, D, G, BS):
    Hq = Hkv * Gq
    k = jax.random.normal(jax.random.key(1), (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (B, S, Hkv, D), jnp.float32)
    q = jax.random.normal(jax.random.key(3), (B, Hq, D), jnp.float32)
    bias = jnp.where(jax.random.uniform(jax.random.key(4), (B, S)) < 0.2,
                     -1e30, 0.0)
    kq, ks, kz = kq_ref.kquant_ref(k, bits, G)
    vq, vs, vz = kq_ref.vquant_ref(v, bits)
    o_ref = dq_ref.decode_qattn_ref(q, kq, ks, kz, vq, vs, vz, bias,
                                    bits=bits, group=G)
    o_ker = dq_kernel.decode_qattn_pallas(q, kq, ks, kz, vq, vs, vz, bias,
                                          bits=bits, group=G, block_s=BS,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_ker),
                               atol=2e-4, rtol=2e-4)


def test_decode_qattn_bf16_query():
    B, S, Hkv, Gq, D, G = 1, 128, 2, 2, 64, 32
    k = jax.random.normal(jax.random.key(1), (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (B, S, Hkv, D), jnp.float32)
    q = jax.random.normal(jax.random.key(3), (B, Hkv * Gq, D),
                          jnp.float32).astype(jnp.bfloat16)
    bias = jnp.zeros((B, S))
    kq, ks, kz = kq_ref.kquant_ref(k, 8, G)
    vq, vs, vz = kq_ref.vquant_ref(v, 8)
    o_ker = dq_kernel.decode_qattn_pallas(q, kq, ks, kz, vq, vs, vz, bias,
                                          bits=8, group=G, block_s=64,
                                          interpret=True)
    o_ref = dq_ref.decode_qattn_ref(q, kq, ks, kz, vq, vs, vz, bias,
                                    bits=8, group=G)
    np.testing.assert_allclose(
        np.asarray(o_ker, np.float32), np.asarray(o_ref, np.float32),
        atol=2e-2)


@pytest.mark.parametrize("bits", [2, 4, 8, 16])
@pytest.mark.parametrize("W", [0, 16])
def test_decode_attn_fused_ring_mass_matches_ref(bits, W):
    """The extended kernel: dense (bits=16) and quantized main stores,
    the residual ring as a trailing online-softmax block, and the
    per-key attention-mass output."""
    B, S, Hkv, Gq, D, G = 2, 128, 2, 4, 64, 32
    Hq = Hkv * Gq
    keys = jax.random.split(jax.random.key(0), 7)
    k = jax.random.normal(keys[0], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(keys[1], (B, S, Hkv, D), jnp.float32)
    q = jax.random.normal(keys[2], (B, Hq, D), jnp.float32)
    bias = jnp.where(jax.random.uniform(keys[3], (B, S)) < 0.2, -1e30, 0.0)
    if W:
        rk = jax.random.normal(keys[4], (B, W, Hkv, D), jnp.float32)
        rv = jax.random.normal(keys[5], (B, W, Hkv, D), jnp.float32)
        rbias = jnp.where(jax.random.uniform(keys[6], (B, W)) < 0.3,
                          -1e30, 0.0)
    else:
        rk = rv = rbias = None
    if bits < 16:
        kk, ks, kz = kq_ref.kquant_ref(k, bits, G)
        vv, vs, vz = kq_ref.vquant_ref(v, bits)
    else:
        kk, vv = k, v
        ks = kz = vs = vz = None
    o_ref, m_ref = dq_ref.decode_attn_ref(
        q, kk, ks, kz, vv, vs, vz, bias, rk, rv, rbias, bits=bits, group=G)
    o_ker, m_ker = dq_kernel.decode_attn_pallas(
        q, kk, ks, kz, vv, vs, vz, bias, rk, rv, rbias, bits=bits, group=G,
        block_s=64, return_mass=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(m_ker), np.asarray(m_ref),
                               atol=2e-4, rtol=2e-4)
    assert m_ker.shape == (B, S + W)
    # mass is a probability decomposition: rows sum to #query heads
    np.testing.assert_allclose(np.asarray(m_ker.sum(-1)),
                               np.full((B,), Hq, np.float32), rtol=1e-4)


def test_decode_attn_fused_block_snapping():
    """Odd main-store lengths still tile: a store that fits the target is
    one block (a block spanning its array is always legal on the chip),
    longer ones split into even sublane-aligned blocks (quantized stores
    in whole groups whose K scales fill whole sublanes)."""
    B, S, Hkv, Gq, D, G = 1, 96, 1, 2, 32, 32
    keys = jax.random.split(jax.random.key(1), 3)
    k = jax.random.normal(keys[0], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(keys[1], (B, S, Hkv, D), jnp.float32)
    q = jax.random.normal(keys[2], (B, Hkv * Gq, D), jnp.float32)
    bias = jnp.zeros((B, S))
    kk, ks, kz = kq_ref.kquant_ref(k, 4, G)
    vv, vs, vz = kq_ref.vquant_ref(v, 4)
    assert dq_kernel.tile(S, 8 * G, 512) == (96, 96)
    assert dq_kernel.tile(S, 8, 64) == (48, 96)
    o_ref, m_ref = dq_ref.decode_attn_ref(
        q, kk, ks, kz, vv, vs, vz, bias, None, None, None, bits=4, group=G)
    o_ker, m_ker = dq_kernel.decode_attn_pallas(
        q, kk, ks, kz, vv, vs, vz, bias, None, None, None, bits=4, group=G,
        block_s=512, return_mass=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(m_ker), np.asarray(m_ref),
                               atol=2e-4)


def _stack_case(L, B, S, Hkv, Gq, D, W, seed=0):
    """A dense layer stack [L, B, S, Hkv, D] (+ ring), q, and a bias with
    ragged lengths (row 0 shorter) and random holes."""
    ks = jax.random.split(jax.random.key(seed), 7)
    k = jax.random.normal(ks[0], (L, B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[1], (L, B, S, Hkv, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, Hkv * Gq, D), jnp.float32)
    lengths = jnp.asarray([S * 2 // 5] + [S] * (B - 1))
    live = ((jnp.arange(S)[None] < lengths[:, None])
            & (jax.random.uniform(ks[3], (B, S)) > 0.2))
    bias = jnp.where(live, 0.0, -1e30)
    ring = (None, None, None)
    if W:
        ring = (jax.random.normal(ks[4], (B, W, Hkv, D), jnp.float32),
                jax.random.normal(ks[5], (B, W, Hkv, D), jnp.float32),
                jnp.where(jax.random.uniform(ks[6], (B, W)) < 0.3,
                          -1e30, 0.0))
    return q, k, v, bias, ring


@pytest.mark.parametrize("W,mass", [(0, False), (0, True), (16, False),
                                    (16, True)])
@pytest.mark.parametrize("Hkv,Gq", [(1, 1), (1, 4), (8, 1), (8, 4)])
def test_decode_attn_layer_stack_matches_ref(Hkv, Gq, W, mass):
    """The dense store read where it lies: layer `layer` of a
    [L, B, S, Hkv, D] stack attended in place equals the materialize
    oracle on that layer, with ragged lengths, a store of 200 rows that
    no 56-row block divides (the last block overhangs it), with and
    without the ring and the mass output."""
    L, B, S, D, layer = 3, 2, 200, 32, 1
    q, k, v, bias, (rk, rv, rbias) = _stack_case(L, B, S, Hkv, Gq, D, W)
    assert dq_kernel.tile(S, 8, 64) == (56, 224)
    o_ref, m_ref = dq_ref.decode_attn_ref(
        q, k[layer], None, None, v[layer], None, None, bias, rk, rv, rbias,
        bits=16, group=1)
    o_ker, m_ker = dq_kernel.decode_attn_pallas(
        q, k, None, None, v, None, None, bias, rk, rv, rbias, bits=16,
        group=1, block_s=64, return_mass=mass, interpret=True,
        layer=jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    if mass:
        np.testing.assert_allclose(np.asarray(m_ker), np.asarray(m_ref),
                                   atol=2e-5, rtol=2e-5)
    else:
        assert m_ker is None


def test_decode_attn_layer_stack_reads_only_its_layer():
    """Every other layer of the stack is NaN: layer j's output stays
    finite and equal to the oracle, so the kernel reads layer j alone
    (and the rows past the store, in the overhanging last block, are
    never let into the softmax)."""
    L, B, S, Hkv, Gq, D = 4, 2, 200, 8, 4, 32
    q, k, v, bias, (rk, rv, rbias) = _stack_case(L, B, S, Hkv, Gq, D, 16,
                                                 seed=3)
    for j in (0, 3):
        other = (jnp.arange(L) != j)[:, None, None, None, None]
        kn, vn = jnp.where(other, jnp.nan, k), jnp.where(other, jnp.nan, v)
        o_ref, m_ref = dq_ref.decode_attn_ref(
            q, k[j], None, None, v[j], None, None, bias, rk, rv, rbias,
            bits=16, group=1)
        o_ker, m_ker = dq_kernel.decode_attn_pallas(
            q, kn, None, None, vn, None, None, bias, rk, rv, rbias,
            bits=16, group=1, block_s=64, return_mass=True, interpret=True,
            layer=jnp.int32(j))
        assert np.isfinite(np.asarray(o_ker)).all()
        assert np.isfinite(np.asarray(m_ker)).all()
        np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(m_ker), np.asarray(m_ref),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# flash_prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 96])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,Hkv,Gq,D,bq,bk", [
    (2, 256, 2, 2, 64, 64, 64),
    (1, 128, 1, 4, 128, 32, 64),
    (1, 256, 4, 1, 64, 128, 32),
])
def test_flash_prefill_matches_ref(window, dtype, B, T, Hkv, Gq, D, bq, bk):
    Hq = Hkv * Gq
    q = jax.random.normal(jax.random.key(1), (B, T, Hq, D), jnp.float32
                          ).astype(dtype)
    k = jax.random.normal(jax.random.key(2), (B, T, Hkv, D), jnp.float32
                          ).astype(dtype)
    v = jax.random.normal(jax.random.key(3), (B, T, Hkv, D), jnp.float32
                          ).astype(dtype)
    o_ref = fp_ref.flash_prefill_ref(q, k, v, window=window)
    o_ker = fp_kernel.flash_prefill_pallas(q, k, v, window=window, bq=bq,
                                           bk=bk, interpret=True)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_ker, np.float32),
                               np.asarray(o_ref, np.float32), atol=atol)


def test_flash_prefill_matches_model_attention():
    """Kernel agrees with the model's chunked-XLA attention path."""
    from repro.nn.attention import gqa_attention
    B, T, Hkv, Gq, D = 1, 128, 2, 2, 32
    Hq = Hkv * Gq
    q = jax.random.normal(jax.random.key(1), (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, T, Hkv, D), jnp.float32)
    o_model = gqa_attention(q, k, v, causal=True, q_chunk=64)
    o_ker = fp_kernel.flash_prefill_pallas(q, k, v, bq=32, bk=32,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_model),
                               atol=1e-5)


@pytest.mark.parametrize("window", [0, 48])
def test_flash_prefill_chunk_matches_square_kernel(window):
    """The rectangular chunked-prefill variant (segment queries at a
    scalar-prefetched offset over the full-prompt key axis, rows beyond
    the segment zero) reproduces the square kernel's rows exactly —
    chunk by chunk, covering a ragged tail."""
    B, T, Hkv, Gq, D, C = 1, 96, 2, 2, 32, 40
    Hq = Hkv * Gq
    q = jax.random.normal(jax.random.key(1), (B, T, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, T, Hkv, D), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, T, Hkv, D), jnp.float32)
    o_sq = fp_kernel.flash_prefill_pallas(q, k, v, window=window, bq=32,
                                          bk=32, interpret=True)
    for c0 in range(0, T, C):
        c1 = min(c0 + C, T)
        kz = k.at[:, c1:].set(0.0)       # scratch rows not yet streamed
        vz = v.at[:, c1:].set(0.0)
        o_ch = fp_kernel.flash_prefill_chunk_pallas(
            q[:, c0:c1], kz, vz, jnp.asarray([c0], jnp.int32),
            window=window, bq=8, bk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(o_ch),
                                   np.asarray(o_sq[:, c0:c1]), atol=1e-5,
                                   err_msg=f"chunk@{c0}")
