"""The serving kernels compile for a TPU v5e at minicpm-2b's widths.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: a block whose last two dims are neither (8, 128)-aligned
nor the array's, or more VMEM than a kernel may use. These tests compile
each kernel of the serving path for a described (not attached) v5e chip —
Hkv = Hq = 36, D = 64, B = 4, a 1024-row main store plus a 16-row ring,
quant group 16 — and check that the compiled program holds the Pallas
kernel (`tpu_custom_call`). One more compiles a whole decode step at
granite-8b's widths and checks that its dense store is updated in place.
Nothing runs; a compile is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler library at a time, and every
test worker imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.cache import FULL
from repro.kernels.decode_qattn import kernel as dq
from repro.kernels.decode_qattn import ops as dq_ops
from repro.nn import model as M
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
def test_layer_stack_decode_kernel_compiles_for_v5e(compiled_text, mass):
    """A layer stack of wide rows (40 KV heads of 128, 3584 rows, a
    128-row ring): the block is cut to fit VMEM (512 rows of K and V,
    double-buffered, would take 20 MB of it)."""
    L, Hkv, S_, W_ = 3, 40, 3584, 128
    def fn(q, k, v, bias, rk, rv, rbias, layer):
        return dq.decode_attn_pallas(q, k, None, None, v, None, None, bias,
                                     rk, rv, rbias, bits=16, group=1,
                                     return_mass=mass,
                                     compute_dtype=jnp.bfloat16, layer=layer)
    kv = ((L, B, S_, Hkv, 128), jnp.bfloat16)
    ring = ((B, W_, Hkv, 128), jnp.bfloat16)
    assert "tpu_custom_call" in compiled_text(
        fn, ((B, Hkv, 128), jnp.bfloat16), kv, kv, ((B, S_), jnp.float32),
        ring, ring, ((B, W_), jnp.float32), ((), jnp.int32))


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


# a store-sized bf16 value of granite's step (16 slots, 3584 rows, 8 KV
# heads of 128): the layer stack, one layer of it, or its transpose
_STORE = re.compile(r"bf16\[(\d+,)*16,(3584,8|8,3584),128\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(")


def _store_copies(text):
    """Instructions of a compiled HLO text that copy or rebuild a
    store-sized value: copies (and async copies), buffer allocations,
    and dynamic-update-slices, fused or not."""
    bad = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or not _STORE.search(m.group(2)):
            continue
        name, op = m.group(1), m.group(3)
        if (op in ("copy", "copy-start", "copy-done", "dynamic-update-slice")
                or (op == "custom-call" and "AllocateBuffer" in line)
                or (op == "fusion" and ("dynamic-update-slice" in name
                                        or "copy" in name))):
            bad.append(f"{op} {name} {m.group(2)}")
    return bad


def test_dense_decode_step_updates_store_in_place(one_chip, no_compile_cache,
                                                   monkeypatch):
    """The decode step at granite-8b's widths (8 KV heads of 128, 4 query
    heads each, 16 slots, a 3584-row dense store, 2 layers), cache
    donated: the kernel is there, and no op copies, transposes or
    restacks the store — the step writes one row per slot and layer."""
    monkeypatch.setattr(dq_ops, "resolve_interpret", lambda flag: False)
    cfg = dataclasses.replace(get_config("granite-8b"), num_layers=2,
                              use_kernels=True)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: M.init_params(jax.random.key(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: M.init_cache(cfg, FULL, 16, 3584)))
    assert cache.attn.k.shape == (2, 1, 16, 3584, 8, 128)
    tok = jax.ShapeDtypeStruct((16, 1), jnp.int32, sharding=one_chip)
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))

    def step(p, c, t, k):
        logits, c = M.decode_step(p, cfg, c, t, FULL, key=k)
        return jnp.argmax(logits, -1), c

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, tok, key).compile().as_text()
    assert "tpu_custom_call" in text
    assert _store_copies(text) == []


# DeepSeek-V2-Lite's latent store in its cell: 32 slots, 14208 rows of
# 512 + 64 values padded to 640 lanes
_LATENT = re.compile(r"bf16\[(\d+,)*32,14208,640\]")


def _latent_cfg():
    from repro.configs.base import MoEConfig, ModelConfig, YarnScaling
    return ModelConfig(
        name="deepseek-v2-lite-2l", arch_type="moe", source="test",
        num_layers=2, d_model=2048, num_heads=16, num_kv_heads=16,
        d_ff=10944, vocab_size=102400, norm_eps=1e-6, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_yarn=YarnScaling(factor=40, original_max_position=4096,
                              mscale=0.707, mscale_all_dim=0.707),
        moe=MoEConfig(num_experts=64, num_experts_per_tok=6, d_expert=1408,
                      num_shared=2, norm_topk=False, first_dense=1),
        use_kernels=True, remat="none")


def test_latent_decode_step_updates_store_in_place(one_chip,
                                                    no_compile_cache,
                                                    monkeypatch):
    """The decode step at DeepSeek-V2-Lite's widths (MLA, the dense layer
    and an expert layer, 32 slots, 14208 rows), cache donated: the latent
    decode kernel and the grouped expert matmul are there under their
    trace names, and no op copies or restacks the latent store."""
    from repro.kernels.decode_mla import ops as mla_ops
    from repro.kernels.moe_gmm import ops as gmm_ops
    monkeypatch.setattr(mla_ops, "resolve_interpret", lambda flag: False)
    monkeypatch.setattr(gmm_ops, "resolve_interpret", lambda flag: False)
    cfg = _latent_cfg()
    spec = dataclasses.replace(FULL, budget=14208)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: M.init_params(jax.random.key(0), cfg)))
    cache = on_chip(jax.eval_shape(lambda: M.init_cache(cfg, spec, 32,
                                                        14208)))
    assert cache.attn.k.shape == (2, 1, 32, 14208, 640)
    tok = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    # the engine's live-slot mask, which only the MoE counters read
    live = jax.ShapeDtypeStruct((32,), jnp.bool_, sharding=one_chip)

    def step(p, c, t, k, live):
        logits, c = M.decode_step(p, cfg, c, t, spec, key=k, live=live)
        return jnp.argmax(logits, -1), c

    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, tok, key, live).compile().as_text()
    assert re.search(r"%decode_mla_pallas(\.\d+)? = ", text)
    assert re.search(r"%moe_gmm(\.\d+)? = ", text)
    bad = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and _LATENT.search(m.group(2)) and (
                m.group(3) in ("copy", "copy-start", "copy-done",
                               "dynamic-update-slice")
                or (m.group(3) == "custom-call" and "AllocateBuffer" in line)
                or "copy" in m.group(1)):
            bad.append(line[:160])
    assert bad == []


def test_latent_prompt_chunk_compiles_for_v5e(one_chip, no_compile_cache,
                                              monkeypatch):
    """A 512-token prompt chunk at DeepSeek-V2-Lite's widths against a
    13,696-row latent scratch compiles for the chip: the grouped expert
    matmul at 3072 assignment rows (a decode step has 192), and the
    chunk's temporaries (the whole scratch expanded to per-head K/V and
    attended, masked) under 2 GiB."""
    from repro.kernels.moe_gmm import ops as gmm_ops
    monkeypatch.setattr(gmm_ops, "resolve_interpret", lambda flag: False)
    cfg = _latent_cfg()
    spec = dataclasses.replace(FULL, budget=14208)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: M.init_params(jax.random.key(0), cfg)))
    st = on_chip(jax.eval_shape(lambda: M.init_prefill_state(cfg, 13696)))
    toks = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    c0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def chunk(p, s, t, c):
        return M.prefill_chunk(p, cfg, s, t, c, spec)

    compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
        params, st, toks, c0).compile()
    assert re.search(r"%moe_gmm(\.\d+)? = ", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
