"""DeepSeek-V2's block (latent attention + routed and shared experts) on
the serving path, against the plain reference `bench/refs/mla_moe_decoder.py`
at a tiny size in float32: 3 layers (one leading dense), 4 experts, top 2,
1 shared, latent rank 32, rope 16 (YaRN).

Tolerances: both sides compute in float32 with the same weights, so they
differ by summation order alone (the reference at `highest` precision,
blocked; the program through its cache, absorbed decode and grouped
matmuls). Logits here are O(1); 2e-4 is some 100x that rounding and far
below any real mistake (a dropped norm, a wrong rope pair, a missed
expert moves logits by 1e-2 or more)."""
import dataclasses
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as kvcache
from repro.core.cache import CacheSpec
from repro.core.policy import presets
from repro.nn import mla
from repro.nn import model as M
from repro.nn import moe as moe_lib
from repro.nn.rope import yarn_freqs
from repro.serving import Engine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4

CONF = {
    "hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
    "moe_intermediate_size": 24, "moe_layer_freq": 1, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": False, "num_attention_heads": 4,
    "num_experts_per_tok": 2, "num_hidden_layers": 3, "num_key_value_heads": 4,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "routed_scaling_factor": 1, "first_k_dense_replace": 1,
    "tie_word_embeddings": False, "vocab_size": 128, "topk_method": "greedy",
    "scoring_func": "softmax", "hidden_act": "silu", "attention_bias": False,
    "n_group": 1, "topk_group": 1, "name": "tinymla", "source": "test",
}


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT,
                                                                     rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("bench/refs/mla_moe_decoder.py", "ref_mla_moe_decoder")


@pytest.fixture(scope="module")
def cfg():
    sys.path.insert(0, os.path.join(ROOT, "bench"))   # archs import counts
    arch = _load("bench/archs/mla_moe_decoder.py", "arch_mla_moe_decoder")
    return dataclasses.replace(arch.program_config(CONF), dtype=jnp.float32,
                               use_kernels=False)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights; norm scales 1 + N(0, 0.1^2), so a path that drops
    one shows in the logits."""
    p = M.init_params(jax.random.key(7), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(p)
    out = []
    for i, (path, x) in enumerate(leaves):
        if path[-1].key == "scale":
            x = 1 + 0.1 * jax.random.normal(jax.random.key(100 + i), x.shape)
        out.append(x)
    return jax.tree_util.tree_unflatten(tree, out)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


def _reference(ref, params, tokens, at):
    T = -(-len(tokens) // 16) * 16
    seq = np.zeros(T, np.int32)
    seq[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.served_logits(
            params, CONF, jnp.asarray(seq), len(tokens), jnp.asarray(at),
            block=16))


def _serve(params, cfg, prompt, fed, *, chunk=None, other=None, slots=2):
    """Logits of slot 0: the prompt (chunked by `chunk`, else monolithic)
    inserted into a live `slots`-wide cache, then one decode step per
    token of `fed`. `other`, a second prompt, occupies slot 1."""
    S = len(prompt) + len(fed) + 8
    spec = CacheSpec(budget=S, policy="none")

    def prefill(p):
        if chunk is None:
            lg, pc = M.prefill(params, cfg, {"tokens": jnp.asarray(p[None])},
                               spec)
            return lg, pc
        st = M.init_prefill_state(cfg, len(p))
        for c0 in range(0, len(p), chunk):
            lg, st = M.prefill_chunk(params, cfg, st,
                                     jnp.asarray(p[None, c0:c0 + chunk]),
                                     jnp.int32(c0), spec)
        return lg, M.prefill_finalize(cfg, st, spec)

    cache = M.init_cache(cfg, spec, slots, S)
    out = []
    for slot, p in ((0, prompt), (1, other)):
        if p is None:
            continue
        lg, pc = prefill(p)        # the store's S rows, as the engine's
        cache = cache._replace(
            attn=kvcache.insert_request(cache.attn, slot, pc.attn,
                                        batch_axis=2),
            moe=moe_lib.add_stats(cache.moe, pc.moe))
        if slot == 0:
            out.append(np.asarray(lg[0]))
    toks = np.zeros((slots, 1), np.int32)
    for t in fed:
        toks[0, 0] = t
        lg, cache = M.decode_step(params, cfg, cache, jnp.asarray(toks), spec)
        out.append(np.asarray(lg[0]))
    return np.stack(out), cache


@pytest.mark.parametrize("use_kernels", [False, True])
def test_prefill_then_decode_matches_reference(ref, params, cfg, use_kernels):
    """Monolithic prefill, then decode steps; with kernels on (interpret
    mode) the grouped matmul reads both MoE layers' experts from the
    layer stack where they lie (blocks 0 and 1)."""
    c = dataclasses.replace(cfg, use_kernels=use_kernels)
    prompt, fed = _prompt(0, 40), _prompt(1, 6)
    got, cache = _serve(params, c, prompt, fed)
    want = _reference(ref, params, np.concatenate([prompt, fed]),
                      np.arange(39, 46))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # the latent store holds one zero-padded row a position and layer
    assert cache.attn.k.shape[-1] == cfg.latent_store_dim == 128
    assert cache.attn.v.shape[-1] == 0
    assert int(cache.moe.assignments) == 2 * 2 * (40 + 2 * 6)
    # 2 MoE layers a prefill or decode step: 1 prefill and 6 steps
    assert int(cache.moe.calls) == 2 * (1 + 6)
    assert int(cache.moe.inplace_calls) == (int(cache.moe.calls)
                                            if use_kernels else 0)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_chunked_prefill_matches_monolithic(params, cfg, use_kernels):
    """Chunks of 16 against one pass: the same latent rows (the scratch
    holds exactly the rows a monolithic prefill computes) and logits; with
    kernels on (interpret mode) the grouped matmul and the decode kernel
    run too."""
    c = dataclasses.replace(cfg, use_kernels=use_kernels)
    prompt, fed = _prompt(2, 48), _prompt(3, 4)
    mono, cm = _serve(params, c, prompt, fed)
    chunked, cc = _serve(params, c, prompt, fed, chunk=16)
    np.testing.assert_allclose(chunked, mono, atol=TOL, rtol=0)
    # rows of O(1): 1e-5 is float32 rounding of the same products summed
    # in another order (a chunk's rows see the same inputs)
    np.testing.assert_allclose(np.asarray(cc.attn.k), np.asarray(cm.attn.k),
                               atol=1e-5, rtol=0)


def test_absorbed_decode_matches_expanded_attention(params, cfg):
    """The decode form (W_UK folded into the query, output mapped back
    through W_UV, MQA over latent rows) against expanded per-head K/V."""
    pa = jax.tree.map(lambda t: t[0], params["blocks"]["sub0"]["attn"])
    h = jax.random.normal(jax.random.key(3), (2, 24, 64))
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    rows = mla.latent_rows(pa, h, cfg, pos)
    q_nope, q_pe = mla.queries(pa, h, cfg, pos)
    want = mla.attend_expanded(pa, q_nope, q_pe, rows, cfg, pos)[:, -1]
    q = mla.absorb_query(pa, q_nope[:, -1:], q_pe[:, -1:], cfg)[:, 0]
    lc = kvcache.init_layer_kv(CacheSpec(budget=32, policy="none"), 2, 32, 0,
                               cfg.latent_store_dim, jnp.float32, v_dim=0)
    lc = lc._replace(k=lc.k.at[:, :24].set(rows),
                     length=jnp.full((2,), 24, jnp.int32))
    for kernels in (False, True):
        o_lat = mla.decode_attention(q, lc, cfg, use_kernels=kernels,
                                     interpret=True)
        got = mla.absorbed_output(pa, o_lat[:, None], cfg)[:, 0]
        # the same float32 products regrouped (W_UK and W_UV applied on
        # the other side of the attention): rounding only
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=0)


def test_slot_logits_independent_of_other_slots(params, cfg):
    """Drop-free routing: slot 0's logits are the same, bit for bit,
    whatever slot 1 holds (nothing, or another prompt that routes
    differently)."""
    prompt, fed = _prompt(4, 32), _prompt(5, 5)
    alone, _ = _serve(params, cfg, prompt, fed)
    beside, _ = _serve(params, cfg, prompt, fed, other=_prompt(6, 32))
    beside4, _ = _serve(params, cfg, prompt, fed, other=_prompt(8, 32),
                        slots=4)
    np.testing.assert_array_equal(beside, alone)
    np.testing.assert_array_equal(beside4, alone)


def test_engine_serves_greedy_tokens_of_the_reference(ref, params, cfg):
    """The whole serving path (Engine, chunked prefill of 16, 2 slots, a
    dense in-place latent store, drop-free experts): every served token
    is the reference's best at its position, within the tolerance."""
    eng = Engine(cfg, params, presets(budget=0, window=16)["full"],
                 buckets=[32, 48], max_new=6, slots=2, chunked_prefill=True,
                 chunk_len=16)
    reqs = [Request(tokens=_prompt(10 + i, n), max_new=6)
            for i, n in enumerate((48, 32, 48))]
    res = eng.generate_continuous(reqs)
    assert res.inplace_steps == res.decode_steps > 0
    assert res.moe["assignments"] > 0 and res.latent_store_bytes > 0
    for q in reqs:
        toks = res.tokens_for(q.uid)
        seq = np.concatenate([q.tokens, toks[:-1]])
        lg = _reference(ref, params, seq,
                        np.arange(len(q.tokens) - 1, len(seq)))
        gap = lg.max(-1) - lg[np.arange(len(toks)), toks]
        assert gap.max() <= TOL, gap


def test_engine_counts_only_live_slots(params, cfg):
    """The MoE counters count prompt rows and the decode rows of slots
    serving a request, not the rows of empty slots a step still
    computes: 3 requests on 2 slots leave one slot empty while the last
    one prefills and drains."""
    eng = Engine(cfg, params, presets(budget=0, window=16)["full"],
                 buckets=[32, 48], max_new=6, slots=2, chunked_prefill=True,
                 chunk_len=16)
    reqs = [Request(tokens=_prompt(20 + i, n), max_new=6)
            for i, n in enumerate((48, 32, 48))]
    res = eng.generate_continuous(reqs)
    per_row = 2 * 2                  # 2 MoE layers x top 2
    rows = 128 + res.decode_tokens   # prompts + recorded decode tokens
    # a slot's last dispatched step may run past its final token (the
    # fetch trails the dispatch by one step): at most one row a request
    assert per_row * rows <= res.moe["assignments"] <= per_row * (rows + 3)
    assert res.moe["assignments"] < per_row * (128 + 2 * res.decode_steps)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_engine_counts_inplace_expert_reads(params, cfg, use_kernels):
    """`moe.inplace_calls` counts every MoE layer call of an engine run
    (prompt chunks and decode steps) when the grouped matmul kernel
    reads the layer-stacked experts in place, and none without it."""
    from repro.obs import Metrics
    mx = Metrics()
    eng = Engine(dataclasses.replace(cfg, use_kernels=use_kernels), params,
                 presets(budget=0, window=16)["full"], buckets=[32],
                 max_new=3, slots=2, chunked_prefill=True, chunk_len=16,
                 metrics=mx)
    res = eng.generate_continuous([Request(tokens=_prompt(30 + i, 32),
                                           max_new=3) for i in range(2)])
    assert res.moe["calls"] > 0
    assert res.moe["inplace_calls"] == (res.moe["calls"] if use_kernels
                                        else 0)
    snap = mx.snapshot()
    assert snap["moe.inplace_calls"] == res.moe["inplace_calls"]
    assert snap["moe.calls"] == res.moe["calls"]


def test_yarn_frequencies_and_scale(cfg):
    """DeepSeek-V2-Lite's YaRN (rope 64, theta 1e4, factor 40 over 4096
    positions, beta 32/1): the correction range d(32) = 10.47 -> 10,
    d(1) = 22.5 -> 23; original frequencies below it, divided by 40
    above it, a linear ramp between; softmax scale 192^-1/2 m^2."""
    y = dataclasses.replace(cfg.rope_yarn, original_max_position=4096)
    inv = np.asarray(yarn_freqs(64, 1e4, y))
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    low, high = 10, 23
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                      / (2 * math.log(1e4))) == low
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e4))) == high
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, extra / 40 * ramp + extra * (1 - ramp),
                               rtol=1e-6)
    assert inv[low] == pytest.approx(extra[low])
    assert inv[high] == pytest.approx(extra[high] / 40)
    c = dataclasses.replace(cfg, qk_nope_head_dim=128, qk_rope_head_dim=64,
                            head_dim=192)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert mla.softmax_scale(c) == pytest.approx(192 ** -0.5 * m * m)


def test_what_is_not_supported_raises(params, cfg):
    kw = dict(buckets=[32], max_new=4, slots=2)
    full = presets(budget=0, window=16)["full"]
    with pytest.raises(ValueError, match="paged latent"):
        Engine(cfg, params, full, paged=True, **kw)
    with pytest.raises(ValueError, match="latent .MLA. store supports no"):
        Engine(cfg, params, presets(budget=32, window=16)["kivi2"], **kw)
    with pytest.raises(ValueError, match="h2o"):
        Engine(cfg, params, presets(budget=32, window=16)["h2o"], **kw)
    with pytest.raises(ValueError, match="routed experts"):
        Engine(cfg, params, full, speculative=True, chunked_prefill=True,
               **kw)
    with pytest.raises(ValueError, match="latent"):
        Engine(dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, num_experts=0)), params, full, speculative=True, **kw)
