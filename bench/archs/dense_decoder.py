"""The program side of the dense pre-norm decoder (granite, minicpm):
in every layer GQA attention and a gated MLP, held by the program's
model as one `blocks.sub0` stack with a leading layer axis.

A configuration file's `reference` names its architecture, and two
files are found by that name: `refs/<name>.py`, the plain reference,
which imports nothing of the program, and `archs/<name>.py`, such as
this one, which tells the rest of the benchmark what it needs to know
of the architecture:

    program_config(conf)   the program's `ModelConfig`
    weight_shapes(conf)    {path: (shape, fan_in or 0 for a norm scale)}
                           of the weight tree the program's model takes;
                           `weights.make` draws it
    work(conf, engine)     the object whose methods give what one token
                           costs; `counts` sums them over the served
                           requests
"""
from __future__ import annotations

from dataclasses import dataclass

import counts


def program_config(conf: dict):
    """The program's `ModelConfig` for a configuration file."""
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff",
            "vocab_size", "head_dim", "tie_embeddings", "norm_eps",
            "rope_theta")
    return ModelConfig(name=conf["name"], arch_type="dense",
                       source=conf["source"], dtype=jnp.bfloat16,
                       remat="none", **{k: conf[k] for k in keys})


def weight_shapes(conf: dict) -> dict:
    """{path: (shape, fan_in or 0 for a norm scale)} of the weight tree:
    embedding table, final norm, the layer stack, and the head where it
    is not tied to the table."""
    L, d, f = conf["num_layers"], conf["d_model"], conf["d_ff"]
    hq = conf["num_heads"] * conf["head_dim"]
    hkv = conf["num_kv_heads"] * conf["head_dim"]
    out = {
        ("embed", "table"): ((conf["vocab_size"], d), d),
        ("final_norm", "scale"): ((d,), 0),
        ("blocks", "sub0", "norm1", "scale"): ((L, d), 0),
        ("blocks", "sub0", "attn", "wq", "w"): ((L, d, hq), d),
        ("blocks", "sub0", "attn", "wk", "w"): ((L, d, hkv), d),
        ("blocks", "sub0", "attn", "wv", "w"): ((L, d, hkv), d),
        ("blocks", "sub0", "attn", "wo", "w"): ((L, hq, d), hq),
        ("blocks", "sub0", "norm2", "scale"): ((L, d), 0),
        ("blocks", "sub0", "mlp", "gate", "w"): ((L, d, f), d),
        ("blocks", "sub0", "mlp", "up", "w"): ((L, d, f), d),
        ("blocks", "sub0", "mlp", "down", "w"): ((L, f, d), f),
    }
    if not conf["tie_embeddings"]:
        out[("head", "w")] = ((d, conf["vocab_size"]), d)
    return out


def work(conf: dict, engine: dict) -> "Work":
    """What one token costs in this configuration, its cache kept as the
    cell's engine keeps it."""
    return Work(layers=conf["num_layers"], d_model=conf["d_model"],
                heads=conf["num_heads"], kv_heads=conf["num_kv_heads"],
                head_dim=conf["head_dim"], d_ff=conf["d_ff"],
                vocab=conf["vocab_size"], store=counts.Store.of(engine))


@dataclass(frozen=True)
class Work:
    """Every layer: q of `heads` and K/V of `kv_heads` heads of
    `head_dim`, a gated MLP of width `d_ff`."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    store: counts.Store = counts.Store()

    def block_params(self) -> int:
        """Parameters of the layer stack that one token's forward pass
        multiplies by: all of them, as every layer is dense (no
        embedding, no head)."""
        hq, hkv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        per = (self.d_model * (hq + 2 * hkv) + hq * self.d_model
               + 3 * self.d_model * self.d_ff + 2 * self.d_model)
        return self.layers * per

    def attn_flops(self, pairs: int) -> int:
        """QK^T and PV over `pairs` (query, key) pairs, every layer."""
        return 4 * pairs * self.heads * self.head_dim * self.layers

    def head_flops(self) -> int:
        """The LM head's FLOPs for one served token."""
        return 2 * self.d_model * self.vocab

    def decode_kv_bytes(self, ctx: int) -> int:
        """Bytes one decode step must read of one slot's cache, all
        layers, with `ctx` keys valid: K and V of `kv_heads` heads of
        `head_dim` per position, as the policy stores them."""
        return self.store.kv_bytes(ctx, self.kv_heads,
                                   self.head_dim) * self.layers

    def decode_qo_bytes(self) -> int:
        """Query read and output written by the decode kernel for one
        slot, all layers."""
        return 2 * self.heads * self.head_dim * 2 * self.layers

    def chunk_io_bytes(self, c0: int, c: int) -> int:
        """Least bytes of one causal prefill chunk of `c` queries from
        position `c0`: its queries and outputs, and the bf16 keys and
        values up to its last position, all layers."""
        q_o = 2 * c * self.heads * self.head_dim * 2
        kv = 2 * (c0 + c) * self.kv_heads * self.head_dim * 2
        return (q_o + kv) * self.layers
