"""The work counts behind rooflines and MFU, against hand-worked values:
the dense decoder's work object, and `counts`' loops over a work object
of another cache layout."""
import counts
import harness
import pytest

DENSE_ARCH = harness.arch("dense_decoder")
DENSE = DENSE_ARCH.Work(layers=1, d_model=8, heads=2, kv_heads=1,
                        head_dim=4, d_ff=16, vocab=32)
KIVI = DENSE_ARCH.Work(layers=2, d_model=8, heads=2, kv_heads=2,
                       head_dim=8, d_ff=16, vocab=32,
                       store=counts.Store(bits=2, group=4))


def test_dense_decode_bytes_and_flops():
    # K and V, 10 rows x 1 head x 4 dims x 2 bytes
    assert DENSE.decode_kv_bytes(10) == 160
    # q read + out written: 2 heads x 4 dims x 2 bytes each
    assert DENSE.decode_qo_bytes() == 32
    # QK^T + PV: 4 x pairs x heads x head_dim x layers
    assert DENSE.attn_flops(10) == 320
    # the LM head: 2 x d_model x vocab
    assert DENSE.head_flops() == 512


def test_kivi_decode_bytes():
    # ctx 10, group 4: 8 rows flushed (2 groups), 2 in the bf16 ring
    codes = 2 * 8 * 2 * 8 * 2 // 8          # K and V codes, 2 bits
    kmeta = 2 * 2 * 2 * 8 * 4               # K scale+zero per group/chan
    vmeta = 2 * 8 * 2 * 4                   # V scale+zero per token/head
    ring = 2 * 2 * 2 * 8 * 2
    assert (codes, kmeta, vmeta, ring) == (64, 256, 128, 128)
    assert KIVI.decode_kv_bytes(10) == 2 * 576
    # a group boundary: the key at position 8 sits alone in the ring
    assert KIVI.decode_kv_bytes(9) == 2 * (
        2 * 8 * 2 * 8 * 2 // 8 + 2 * 2 * 2 * 8 * 4 + 2 * 8 * 2 * 4
        + 2 * 1 * 2 * 8 * 2)


def test_model_flops_hand_worked():
    r = counts.Served(prompt=4, served=3)
    # layer stack: qkv 8*(8+4+4) + wo 8*8 + mlp 3*8*16 + norms 2*8
    assert DENSE.block_params() == 592
    # forwarded tokens 4 + 3 - 1 = 6; causal pairs 10 (prompt) + 5 + 6
    # (decode feeds at positions 4 and 5); head for 3 served tokens
    want = 2 * 592 * 6 + 4 * 21 * 2 * 4 + 2 * 8 * 32 * 3
    assert want == 9312
    assert counts.model_flops([r], DENSE) == want


def test_decode_and_prefill_need():
    r = counts.Served(prompt=4, served=3)
    f, b = counts.decode_attn_need([r], DENSE)
    assert f == 4 * (5 + 6) * 2 * 4
    assert b == 2 * 5 * 4 * 2 + 2 * 6 * 4 * 2 + 2 * 32
    # two chunks of 2: pairs 3 then 2*2 + 3 = 7
    assert counts.prefill_chunks(4, 2) == [(0, 2), (2, 2)]
    f, b = counts.prefill_attn_need([r], DENSE, chunk=2)
    assert f == 4 * (3 + 7) * 2 * 4
    assert b == (2 * 2 * 2 * 4 * 2 + 2 * 2 * 4 * 2) + (
        2 * 2 * 2 * 4 * 2 + 2 * 4 * 4 * 2)


class _Latent:
    """A latent-attention layout: one bf16 row of 576 values a position
    and layer (512 latent + 64 rope), read whole by every head; no K/V
    split and no head_dim."""
    layers = 3

    def block_params(self):
        return 1000

    def attn_flops(self, pairs):
        return 7 * pairs * self.layers

    def head_flops(self):
        return 50

    def decode_kv_bytes(self, ctx):
        return ctx * 576 * 2 * self.layers

    def decode_qo_bytes(self):
        return 11

    def chunk_io_bytes(self, c0, c):
        return (c0 + c) * 576 * 2 * self.layers + 13 * c


def test_loops_assume_no_kv_layout():
    reqs = [counts.Served(prompt=4, served=3), counts.Served(prompt=2,
                                                             served=1)]
    # forwarded 6 + 2 tokens; pairs (10 + 5 + 6) + 3; served 3 + 1
    assert counts.model_flops(reqs, _Latent()) == (
        2 * 1000 * 8 + 7 * 24 * 3 + 50 * 4)
    # decode steps only for the first request, at contexts 5 and 6
    f, b = counts.decode_attn_need(reqs, _Latent())
    assert f == 7 * (5 + 6) * 3
    assert b == (5 + 6) * 576 * 2 * 3 + 2 * 11
    # chunks of 2: (0, 2), (2, 2) and (0, 2); pairs 3, 7, 3
    f, b = counts.prefill_attn_need(reqs, _Latent(), chunk=2)
    assert f == 7 * (3 + 7 + 3) * 3
    assert b == (2 + 4 + 2) * 576 * 2 * 3 + 13 * 6


def test_roofline_share_names_its_bound():
    pk = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    share, bound = counts.roofline_share(197e9, 2 * 819e6, 0.004, pk)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = counts.roofline_share(4 * 197e9, 819e6, 0.008, pk)
    assert bound == "compute" and share == pytest.approx(50.0)


def test_shape_of_files():
    s = DENSE_ARCH.work(
        dict(num_layers=40, d_model=2304, num_heads=36, num_kv_heads=36,
             head_dim=64, d_ff=5760, vocab_size=122753,
             tie_embeddings=True),
        {"policy": "kivi2", "window": 128})
    assert s.store == counts.Store(bits=2, group=128)
    # 2.72B parameters in all: the layer stack plus the tied table
    assert s.block_params() + 122753 * 2304 == pytest.approx(2.72e9,
                                                             rel=0.01)
