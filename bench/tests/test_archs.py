"""The dense decoder behind the architecture seam gives the numbers it
gave before the seam: the same work counts to the integer, the same
weight tree key for key, and the same weight draw bit for bit. Every
pinned number here was computed with the counts and weights of the
commit before `archs/` existed."""
import hashlib
import json
import math
import os

import counts
import harness
import jax
import numpy as np
import pytest
import weights

DENSE = harness.arch("dense_decoder")
CONFIGS = os.path.join(harness.HERE, "configs")
CELLS = os.path.join(harness.HERE, "cells")
DATA = os.path.join(harness.HERE, "tests", "data")
PINNED = [counts.Served(3456, 128), counts.Served(3456, 64)]


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# (config, engine) -> block_params, model_flops, decode_attn_need,
# prefill_attn_need at chunks of 512, over PINNED
IDENTITY = [
    ((CONFIGS, "granite-8b-12l.json"),
     (CELLS, "granite-8b-12l.full.longdoc.json"),
     2_617_344_000, 39_734_111_502_336,
     (131_095_068_672, 32_811_122_688), (2_348_952_846_336, 2_755_657_728)),
    ((CONFIGS, "minicpm-2b.json"), (CELLS, "minicpm-2b.kivi2.longdoc.json"),
     2_442_055_680, 39_445_652_717_568,
     (245_803_253_760, 45_414_236_160), (4_404_286_586_880, 13_023_313_920)),
    ((DATA, "tiny.json"), (DATA, "tiny.full.json"),
     295_424, 17_138_284_544,
     (682_786_816, 341_587_968), (12_234_129_408, 21_626_880)),
    ((DATA, "tiny.json"), (DATA, "tiny.kivi2.json"),
     295_424, 17_138_284_544,
     (682_786_816, 73_678_848), (12_234_129_408, 21_626_880)),
]


@pytest.mark.parametrize("conf,cell,params,flops,decode,prefill", IDENTITY,
                         ids=["granite.full", "minicpm.kivi2", "tiny.full",
                              "tiny.kivi2"])
def test_work_counts_identity(conf, cell, params, flops, decode, prefill):
    m = _load(*conf)
    assert m["reference"] == "dense_decoder"
    w = DENSE.work(m, _load(*cell)["engine"])
    assert w.block_params() == params
    assert counts.model_flops(PINNED, w) == flops
    assert counts.decode_attn_need(PINNED, w) == decode
    assert counts.prefill_attn_need(PINNED, w, 512) == prefill


TINY_SPEC = {
    ("blocks", "sub0", "attn", "wk", "w"): ((2, 128, 64), 128),
    ("blocks", "sub0", "attn", "wo", "w"): ((2, 128, 128), 128),
    ("blocks", "sub0", "attn", "wq", "w"): ((2, 128, 128), 128),
    ("blocks", "sub0", "attn", "wv", "w"): ((2, 128, 64), 128),
    ("blocks", "sub0", "mlp", "down", "w"): ((2, 256, 128), 256),
    ("blocks", "sub0", "mlp", "gate", "w"): ((2, 128, 256), 128),
    ("blocks", "sub0", "mlp", "up", "w"): ((2, 128, 256), 128),
    ("blocks", "sub0", "norm1", "scale"): ((2, 128), 0),
    ("blocks", "sub0", "norm2", "scale"): ((2, 128), 0),
    ("embed", "table"): ((512, 128), 128),
    ("final_norm", "scale"): ((128,), 0),
}


def test_weight_shapes_identity():
    tiny = _load(DATA, "tiny.json")
    assert DENSE.weight_shapes(tiny) == TINY_SPEC
    untied = DENSE.weight_shapes({**tiny, "tie_embeddings": False})
    assert untied == {**TINY_SPEC, ("head", "w"): ((128, 512), 128)}
    # the full-width trees: leaves, parameters, and a digest of the
    # sorted {path: (shape, fan_in)} items
    for name, leaves, n, digest in (
            ("granite-8b-12l", 11, 2_818_674_688, "dbc4bade9c52608d"),
            ("minicpm-2b", 11, 2_724_880_896, "4a1131764aeb5241")):
        spec = DENSE.weight_shapes(_load(CONFIGS, f"{name}.json"))
        assert len(spec) == leaves
        assert sum(math.prod(s) for s, _ in spec.values()) == n
        assert hashlib.sha256(repr(sorted(spec.items())).encode()
                              ).hexdigest()[:16] == digest


# sha256 (first 16 hex digits) of each bf16 leaf `weights.make` draws
# for tiny.json at seed 2**32 + 5 (both halves of the seed in use)
TINY_DRAW = {
    "blocks/sub0/attn/wk/w": "4a64c0c28ea16889",
    "blocks/sub0/attn/wo/w": "9c1494915bebaaf2",
    "blocks/sub0/attn/wq/w": "b3db4d47695dbfea",
    "blocks/sub0/attn/wv/w": "6e926ae5306f9e79",
    "blocks/sub0/mlp/down/w": "9ba73fdfe9592d30",
    "blocks/sub0/mlp/gate/w": "7c7f55f3f80678fe",
    "blocks/sub0/mlp/up/w": "cc6463a890dbe3bc",
    "blocks/sub0/norm1/scale": "649f7d33e662a837",
    "blocks/sub0/norm2/scale": "067d6ae861dea224",
    "embed/table": "fe433e648b128bfd",
    "final_norm/scale": "15370223be718f3a",
}


def _leaves(tree):
    return {"/".join(k.key for k in path): x for path, x
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_weight_draw_identity(tiny_root):
    c = harness.load_cell("tiny.full.tinymix", tiny_root)
    got = _leaves(weights.make(c, 2**32 + 5))
    assert {k: hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
            for k, x in got.items()} == TINY_DRAW
    assert all(x.dtype == jax.numpy.bfloat16 for x in got.values())
    shapes = {k: (s.shape, s.dtype) for k, s
              in _leaves(weights.abstract(c)).items()}
    assert shapes == {k: (x.shape, x.dtype) for k, x in got.items()}
