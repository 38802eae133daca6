"""The serving kernels compile for a TPU v5e at minicpm-2b's widths.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: a block whose last two dims are neither (8, 128)-aligned
nor the array's, or more VMEM than a kernel may use. These tests compile
each kernel of the serving path for a described (not attached) v5e chip —
Hkv = Hq = 36, D = 64, B = 4, a 1024-row main store plus a 16-row ring,
quant group 16 — and check that the compiled program holds the Pallas
kernel (`tpu_custom_call`). Nothing runs; a compile is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler library at a time, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_qattn import kernel as dq
from repro.kernels.flash_prefill import ops as fp

B, H, D, S, W, G = 4, 36, 64, 1024, 16, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compiled_text(one_chip, no_compile_cache):
    def compile_(fn, *shapes):
        args = [None if s is None else
                jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
                for s in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return compile_


def _main_store(bits, rows_lead):
    """Shapes of (k, k_scale, k_zero, v, v_scale, v_zero) for a store whose
    leading dims are `rows_lead` (dense: (B, S); paged: (n_blocks, bl))."""
    if bits == 16:
        kv = (rows_lead + (H, D), jnp.bfloat16)
        return [kv, None, None, kv, None, None]
    n, rows = rows_lead
    kv = (rows_lead + (H, D * bits // 8), jnp.int8)
    ks = ((n, rows // G, H, D), jnp.float32)
    vs = (rows_lead + (H,), jnp.float32)
    return [kv, ks, ks, kv, vs, vs]


def _ring():
    r = ((B, W, H, D), jnp.bfloat16)
    return [r, r, ((B, W), jnp.float32)]


@pytest.mark.parametrize("mass", [False, True])
@pytest.mark.parametrize("bits", [16, 2])
def test_decode_kernel_compiles_for_v5e(compiled_text, bits, mass):
    def fn(*a):
        return dq.decode_attn_pallas(*a, bits=bits, group=G,
                                     return_mass=mass,
                                     compute_dtype=jnp.bfloat16)
    text = compiled_text(fn, ((B, H, D), jnp.bfloat16),
                         *_main_store(bits, (B, S)),
                         ((B, S), jnp.float32), *_ring())
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mass", [False, True])
@pytest.mark.parametrize("bits", [16, 2])
def test_paged_decode_kernel_compiles_for_v5e(compiled_text, bits, mass):
    n_max = S // G
    def fn(*a):
        return dq.decode_attn_paged_pallas(*a, bits=bits, group=G,
                                           return_mass=mass,
                                           compute_dtype=jnp.bfloat16)
    text = compiled_text(fn, ((B, H, D), jnp.bfloat16),
                         ((B, n_max), jnp.int32),
                         *_main_store(bits, (B * n_max, G)),
                         ((B, S), jnp.float32), *_ring())
    assert "tpu_custom_call" in text


def test_flash_prefill_compiles_for_v5e(compiled_text):
    def fn(q, k, v):
        return fp.flash_attention(q, k, v, interpret=False)
    x = ((B, 512, H, D), jnp.bfloat16)
    assert "tpu_custom_call" in compiled_text(fn, x, x, x)


def test_flash_prefill_chunk_compiles_for_v5e(compiled_text):
    def fn(q, k, v, off):
        return fp.flash_attention_chunk(q, k, v, q_offset=off,
                                        interpret=False)
    kv = ((B, 512, H, D), jnp.bfloat16)
    assert "tpu_custom_call" in compiled_text(
        fn, ((B, 64, H, D), jnp.bfloat16), kv, kv, ((1,), jnp.int32))


def test_flash_verify_compiles_for_v5e(compiled_text):
    """Verify over the materialized [main | ring] view: 1040 keys, which
    no sublane-aligned block of at most 512 divides (the key axis is
    padded to its tile)."""
    def fn(q, k, v, kv_pos, bias, q_pos):
        return fp.flash_verify(q, k, v, kv_pos, bias, q_pos,
                               interpret=False)
    kv = ((B, S + W, H, D), jnp.bfloat16)
    assert "tpu_custom_call" in compiled_text(
        fn, ((B, 5, H, D), jnp.bfloat16), kv, kv,
        ((B, S + W), jnp.int32), ((B, S + W), jnp.float32),
        ((B, 5), jnp.int32))
