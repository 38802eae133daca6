"""The program side of DeepSeek-V2's decoder: multi-head latent
attention (MLA) in every layer, a dense gated MLP in the leading
`first_k_dense_replace` layers and routed plus shared experts in the
rest. The program's model holds the leading layers as a `lead.sub0`
stack and the expert layers as `blocks.sub0`, each with a leading layer
axis. The configuration file keeps the model's own config.json keys.

See `archs/dense_decoder.py` for what an architecture module provides.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import counts


def _check(conf: dict) -> None:
    """The parts of the config.json this program models; anything else
    is refused rather than run as something it is not."""
    want = {"q_lora_rank": None, "topk_method": "greedy",
            "scoring_func": "softmax", "hidden_act": "silu",
            "attention_bias": False, "n_group": 1, "topk_group": 1,
            "routed_scaling_factor": 1}
    bad = {k: conf.get(k) for k, v in want.items() if conf.get(k) != v}
    if bad or conf["rope_scaling"].get("type") != "yarn":
        raise ValueError(f"mla_moe_decoder models no {bad or 'rope type'}")


def program_config(conf: dict):
    """The program's `ModelConfig` for a configuration file."""
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig, MoEConfig, YarnScaling
    _check(conf)
    rs = conf["rope_scaling"]
    return ModelConfig(
        name=conf["name"], arch_type="moe", source=conf["source"],
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], rope_theta=float(conf["rope_theta"]),
        kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        rope_yarn=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        moe=MoEConfig(
            num_experts=conf["n_routed_experts"],
            num_experts_per_tok=conf["num_experts_per_tok"],
            d_expert=conf["moe_intermediate_size"],
            layer_period=conf["moe_layer_freq"],
            num_shared=conf["n_shared_experts"],
            norm_topk=conf["norm_topk_prob"],
            first_dense=conf["first_k_dense_replace"]),
        dtype=jnp.bfloat16, remat="none")


def weight_shapes(conf: dict) -> dict:
    """{path: (shape, fan_in or 0 for a norm scale)} of the weight tree:
    embedding, final norm, head, the leading dense layers (`lead`) and
    the expert layers (`blocks`)."""
    _check(conf)
    d, V = conf["hidden_size"], conf["vocab_size"]
    H, r = conf["num_attention_heads"], conf["kv_lora_rank"]
    dn, dr = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    dv = conf["v_head_dim"]
    E, fe = conf["n_routed_experts"], conf["moe_intermediate_size"]
    fs = conf["n_shared_experts"] * fe
    n_lead = conf["first_k_dense_replace"]
    out = {("embed", "table"): ((V, d), d),
           ("final_norm", "scale"): ((d,), 0)}
    if not conf["tie_word_embeddings"]:
        out[("head", "w")] = ((d, V), d)
    for grp, n in (("lead", n_lead),
                   ("blocks", conf["num_hidden_layers"] - n_lead)):
        if not n:
            continue
        b = (grp, "sub0")
        out.update({
            b + ("norm1", "scale"): ((n, d), 0),
            b + ("attn", "wq", "w"): ((n, d, H * (dn + dr)), d),
            b + ("attn", "wkva", "w"): ((n, d, r + dr), d),
            b + ("attn", "kva_norm", "scale"): ((n, r), 0),
            b + ("attn", "wkvb", "w"): ((n, r, H * (dn + dv)), r),
            b + ("attn", "wo", "w"): ((n, H * dv, d), H * dv),
            b + ("norm2", "scale"): ((n, d), 0),
        })
        if grp == "lead":
            f = conf["intermediate_size"]
            out.update({b + ("mlp", "gate", "w"): ((n, d, f), d),
                        b + ("mlp", "up", "w"): ((n, d, f), d),
                        b + ("mlp", "down", "w"): ((n, f, d), f)})
            continue
        out.update({
            b + ("moe", "router"): ((n, d, E), d),
            b + ("moe", "gate"): ((n, E, d, fe), d),
            b + ("moe", "up"): ((n, E, d, fe), d),
            b + ("moe", "down"): ((n, E, fe, d), fe),
        })
        if fs:
            out.update({b + ("moe", "shared", "gate", "w"): ((n, d, fs), d),
                        b + ("moe", "shared", "up", "w"): ((n, d, fs), d),
                        b + ("moe", "shared", "down", "w"): ((n, fs, d),
                                                              fs)})
    return out


def work(conf: dict, engine: dict) -> "Work":
    """What one token costs in this configuration. Only the full policy
    serves a latent store, so every cached row is one bf16 latent row."""
    if engine["policy"] != "full":
        raise ValueError("a latent store is served with the full policy")
    return Work(layers=conf["num_hidden_layers"],
                lead=conf["first_k_dense_replace"],
                d_model=conf["hidden_size"],
                heads=conf["num_attention_heads"],
                kv_lora=conf["kv_lora_rank"],
                nope=conf["qk_nope_head_dim"], rope=conf["qk_rope_head_dim"],
                v_dim=conf["v_head_dim"], d_ff=conf["intermediate_size"],
                experts=conf["n_routed_experts"],
                top_k=conf["num_experts_per_tok"],
                d_expert=conf["moe_intermediate_size"],
                shared=conf["n_shared_experts"], vocab=conf["vocab_size"])


@dataclass(frozen=True)
class Work:
    """MLA in every layer: `heads` queries of nope + rope, one cached
    latent row of kv_lora + rope a position. A gated MLP of `d_ff` in the
    `lead` layers, then `experts` routed experts of `d_expert` (top_k a
    token, all held on this chip) and `shared` shared ones.

    Attention takes one path a phase. A prompt is `expanded`: per head,
    QK^T over nope + rope and PV over v_dim. A decode step is `absorbed`:
    per head, QK^T over the whole latent row (kv_lora + rope) and PV over
    kv_lora. `attn_flops` counts the expanded form (the least work, what
    MFU counts); `decode()` gives this object with the absorbed form, as
    the decode kernel's roofline counts it."""
    layers: int
    lead: int
    d_model: int
    heads: int
    kv_lora: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    experts: int
    top_k: int
    d_expert: int
    shared: int
    vocab: int
    absorbed: bool = False
    store: counts.Store = counts.Store()

    def decode(self) -> "Work":
        return replace(self, absorbed=True)

    def attn_params(self) -> int:
        """One layer's attention weights and norm scales (kv norm and
        the block's two norms)."""
        d, h = self.d_model, self.heads
        return (d * h * (self.nope + self.rope)          # Wq
                + d * (self.kv_lora + self.rope)         # Wkva
                + self.kv_lora * h * (self.nope + self.v_dim)  # Wkvb
                + h * self.v_dim * d                     # Wo
                + self.kv_lora + 2 * d)                  # norms

    def block_params(self) -> int:
        """Parameters one token's forward pass multiplies by: attention
        in every layer, the dense MLP in the leading layers, and in the
        expert layers the router, the shared experts and top_k of the
        routed ones (all `experts` are held here: k * held / routed =
        top_k)."""
        d, f = self.d_model, self.d_expert
        expert = (d * self.experts + 3 * d * f * self.shared
                  + 3 * d * f * self.top_k)
        return (self.layers * self.attn_params()
                + self.lead * 3 * d * self.d_ff
                + (self.layers - self.lead) * expert)

    def attn_flops(self, pairs: int) -> int:
        """QK^T and PV over `pairs` (query, key) pairs, every layer, in
        this object's form (see the class)."""
        if self.absorbed:
            qk, pv = self.kv_lora + self.rope, self.kv_lora
        else:
            qk, pv = self.nope + self.rope, self.v_dim
        return 2 * pairs * self.heads * (qk + pv) * self.layers

    def head_flops(self) -> int:
        """The LM head's FLOPs for one served token."""
        return 2 * self.d_model * self.vocab

    def decode_kv_bytes(self, ctx: int) -> int:
        """Bytes one decode step must read of one slot's cache, all
        layers, with `ctx` keys valid: one bf16 latent row of kv_lora +
        rope a position (the value is the row's first kv_lora lanes)."""
        return ctx * (self.kv_lora + self.rope) * 2 * self.layers

    def decode_qo_bytes(self) -> int:
        """The decode kernel's absorbed queries read (bf16) and latent
        outputs written (f32) for one slot, all layers."""
        h = self.heads
        return (h * (self.kv_lora + self.rope) * 2
                + h * self.kv_lora * 4) * self.layers

    def chunk_io_bytes(self, c0: int, c: int) -> int:
        """Least bytes of one expanded prefill chunk of `c` queries from
        position `c0`: its queries and outputs, and the latent rows up to
        its last position, all layers."""
        h = self.heads
        q_o = c * h * (self.nope + self.rope + self.v_dim) * 2
        return (q_o + (c0 + c) * (self.kv_lora + self.rope) * 2) * self.layers

    def expert_need(self, assignments: int, experts_hit: int) -> tuple:
        """(FLOPs, bytes) of the routed experts' grouped matmuls for
        `assignments` token-expert pairs that hit `experts_hit` experts
        (summed over calls): gate, up and down for every pair, every hit
        expert's three bf16 matrices read once, and the pairs' bf16 rows
        in and out."""
        d, f = self.d_model, self.d_expert
        flops = 6 * d * f * assignments
        byts = experts_hit * 3 * d * f * 2 + assignments * d * 2 * 2
        return flops, byts
