"""The latent-attention + expert architecture (`archs/mla_moe_decoder.py`,
`refs/mla_moe_decoder.py`): its work counts to the integer, its weight
tree key for key against the program's `init_params`, a tiny draw bit for
bit, and a tiny cell of it run through the harness on the CPU to
`correct: true`."""
import hashlib
import json
import math
import os
import shutil
import time

import counts
import harness
import jax
import numpy as np
import pytest
import weights

ARCH = harness.arch("mla_moe_decoder")
CONFIGS = os.path.join(harness.HERE, "configs")
DATA = os.path.join(harness.HERE, "tests", "data")
PINNED = [counts.Served(13696, 512), counts.Served(2688, 512)]
FULL = {"policy": "full"}
CELL = "tinymla.full.tinymix"


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


DEEPSEEK = _load(CONFIGS, "deepseek-v2-lite-7l.json")
TINY = _load(DATA, "tinymla.json")


def test_deepseek_work_counts():
    w = ARCH.work(DEEPSEEK, FULL)
    # attention + norms a layer: Wq 2048*16*192, Wkva 2048*576, Wkvb
    # 512*16*256, Wo 16*128*2048, norms 512 + 2*2048
    assert w.attn_params() == 13_767_168
    # + layer 0's MLP 3*2048*10944; 6 expert layers of router 2048*64,
    # 2 shared and 6 routed experts of 3*2048*1408
    assert w.block_params() == (7 * 13_767_168 + 3 * 2048 * 10944
                                + 6 * (2048 * 64 + 8 * 3 * 2048 * 1408))
    assert w.block_params() == 579_632_640
    # a decode step reads one 576-value bf16 row a position and layer
    assert w.decode_kv_bytes(10) == 10 * 576 * 2 * 7
    # expanded (prompt) vs absorbed (decode) attention per pair
    assert w.attn_flops(1) == 2 * 16 * (192 + 128) * 7
    assert w.decode().attn_flops(1) == 2 * 16 * (576 + 512) * 7
    assert w.decode_qo_bytes() == (16 * 576 * 2 + 16 * 512 * 4) * 7
    assert w.head_flops() == 2 * 2048 * 102400
    assert w.expert_need(10, 3) == (6 * 2048 * 1408 * 10,
                                    3 * 3 * 2048 * 1408 * 2
                                    + 10 * 2048 * 2 * 2)
    # PINNED: forwarded tokens 14207 + 3199; causal pairs of the prompts
    # plus their 511 decode feeds each; the head for 1024 served tokens
    tok, pairs = 17_406, 106_044_928
    assert counts.model_flops(PINNED, w) == (
        2 * 579_632_640 * tok + pairs * 2 * 16 * 320 * 7
        + 2 * 2048 * 102400 * 1024) == 28_208_968_632_320
    # decode feeds: 511 a request, attending 13697..14207 and 2689..3199
    # keys; absorbed FLOPs, latent rows + absorbed q and latent out
    dec = sum(sum(range(p + 1, p + 512)) for p in (13696, 2688))
    assert counts.decode_attn_need(PINNED, w.decode()) == (
        dec * 2 * 16 * 1088 * 7,
        dec * 576 * 2 * 7 + 1022 * (16 * 576 * 2 + 16 * 512 * 4) * 7) == (
        2_104_174_313_472, 69_989_699_584)
    assert counts.prefill_attn_need(PINNED, w, 512) == (
        6_982_425_640_960, 2_817_654_784)


def test_weight_tree_is_the_programs():
    from repro.nn import model as M
    for conf in (TINY, DEEPSEEK):
        cfg = ARCH.program_config(conf)
        tree = jax.eval_shape(lambda: M.init_params(jax.random.key(0), cfg))
        got = {tuple(k.key for k in p): x.shape for p, x
               in jax.tree_util.tree_flatten_with_path(tree)[0]}
        spec = ARCH.weight_shapes(conf)
        assert got == {p: s for p, (s, _) in spec.items()}
    spec = ARCH.weight_shapes(DEEPSEEK)
    assert len(spec) == 27
    # embedding + head 2 * 102400 * 2048, final norm, layer 0 of
    # 81,007,104 and six expert layers of 584,847,872
    assert sum(math.prod(s) for s, _ in spec.values()) == 4_009_526_784


def test_refuses_what_it_does_not_model():
    for k, v in (("q_lora_rank", 1536), ("topk_method", "group_limited"),
                 ("scoring_func", "sigmoid"),
                 ("routed_scaling_factor", 16.0)):
        with pytest.raises(ValueError, match=k):
            ARCH.program_config({**TINY, k: v})
    with pytest.raises(ValueError, match="full policy"):
        ARCH.work(TINY, {"policy": "kivi2"})


# sha256 (first 16 hex digits) of each bf16 leaf `weights.make` draws
# for tinymla.json at seed 2**32 + 5
TINY_DRAW = {
    "blocks/sub0/attn/kva_norm/scale": "c8bc02e407f8a3ed",
    "blocks/sub0/attn/wkva/w": "eed3ee188b45bcb1",
    "blocks/sub0/attn/wkvb/w": "1504138ad6da6824",
    "blocks/sub0/attn/wo/w": "17120e90b6cae9cd",
    "blocks/sub0/attn/wq/w": "f5d5ad9fd840b389",
    "blocks/sub0/moe/down": "6131b40458637b8b",
    "blocks/sub0/moe/gate": "c7a04e3f4f04b613",
    "blocks/sub0/moe/router": "66b88f89bb9872b7",
    "blocks/sub0/moe/shared/down/w": "b5c2a94f1ddfea9c",
    "blocks/sub0/moe/shared/gate/w": "842addb54a167eb6",
    "blocks/sub0/moe/shared/up/w": "03984123de858c7d",
    "blocks/sub0/moe/up": "02d9af5f87ba9a24",
    "blocks/sub0/norm1/scale": "649dc516a6e3c5e5",
    "blocks/sub0/norm2/scale": "6ecc8c13e6dda33f",
    "embed/table": "a825d8206d59352c",
    "final_norm/scale": "1b3756f9892ed9c3",
    "head/w": "b68cb4cf72a127c8",
    "lead/sub0/attn/kva_norm/scale": "e8d2b4f573b4a170",
    "lead/sub0/attn/wkva/w": "9bc05753e272c52e",
    "lead/sub0/attn/wkvb/w": "aa3cb326388a62c7",
    "lead/sub0/attn/wo/w": "97eac079a1b1a5b7",
    "lead/sub0/attn/wq/w": "30b41d1c759287aa",
    "lead/sub0/mlp/down/w": "ed60f0178e4019ad",
    "lead/sub0/mlp/gate/w": "8ff4051e9c8d59b4",
    "lead/sub0/mlp/up/w": "f6272ed7a21d626f",
    "lead/sub0/norm1/scale": "778cc27322837916",
    "lead/sub0/norm2/scale": "5b16fba3d55cad17",
}


def _leaves(tree):
    return {"/".join(k.key for k in path): x for path, x
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def mla_root(tiny_root, tmp_path_factory):
    """The tiny checkout plus the tiny latent-attention configuration and
    its cell under the tiny mix."""
    dst = str(tmp_path_factory.mktemp("mla_checkout"))
    shutil.copytree(tiny_root, dst, dirs_exist_ok=True)
    b = os.path.join(dst, "bench")
    shutil.copy(os.path.join(DATA, "tinymla.json"),
                os.path.join(b, "configs", "tinymla.json"))
    shutil.copy(os.path.join(DATA, "tinymla.full.json"),
                os.path.join(b, "cells", f"{CELL}.json"))
    bench = _load(dst, "BENCHMARK.json")
    bench["configs"].append({"name": "tinymla", "source": "test",
                             "file": "bench/configs/tinymla.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tinymla",
                               "traffic": "tinymix", "chips": 1,
                               "why": "test"})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


def test_tiny_draw(mla_root):
    c = harness.load_cell(CELL, mla_root)
    got = _leaves(weights.make(c, 2**32 + 5))
    sums = {k: hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
            for k, x in got.items()}
    assert sums == TINY_DRAW
    assert all(x.dtype == jax.numpy.bfloat16 for x in got.values())


def test_tiny_cell_end_to_end(mla_root):
    keep = {}
    line = harness.run(CELL, 2**31 + 11, 0.5, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       root=mla_root, log=lambda *a, **k: None, keep=keep)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert set(line["metrics"]) == {"setup_s", "output_tok_s", "itl_p95_ms"}
    for _, res in keep["ctx"].jobs:
        assert res.moe["assignments"] > 0 and res.latent_store_bytes > 0
        assert res.inplace_steps == res.decode_steps
