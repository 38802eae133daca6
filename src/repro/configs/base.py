"""Model / run configuration system.

Every assigned architecture gets a module in ``repro.configs`` exporting a
``CONFIG: ModelConfig``; the registry below resolves ``--arch <id>``.

Input shapes are the four assigned workload shapes; ``input_specs`` builds
``jax.ShapeDtypeStruct`` stand-ins for the dry-run (no allocation).
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field
from typing import Any, Optional

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0
    num_experts_per_tok: int = 0
    d_expert: int = 0              # hidden dim of each expert MLP
    layer_period: int = 1          # every Nth layer is MoE (jamba: 2)
    router_aux_coef: float = 0.01  # load-balance aux loss
    router_z_coef: float = 1e-3
    capacity_factor: float = 1.25  # expert capacity (E == drop-free)
    num_shared: int = 0            # shared experts, fused into one MLP of
                                   # width num_shared * d_expert (DeepSeek)
    norm_topk: bool = True         # renormalize the top-k weights to sum 1
    first_dense: int = 0           # leading dense layers of width d_ff
                                   # (DeepSeek's first_k_dense_replace)


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2 applies it."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk_size: int = 256
    dt_min: float = 1e-3
    dt_max: float = 0.1


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                 # dense | moe | ssm | hybrid | vlm | audio
    source: str                    # citation for the config
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 -> d_model // num_heads
    qkv_bias: bool = False
    attn_out_bias: bool = False
    mlp_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 10_000.0
    sliding_window: int = 0        # 0 = full attention
    # multi-head latent attention (DeepSeek-V2, arXiv:2405.04434): K/V of
    # every head are expanded from one cached latent of `kv_lora_rank`
    # plus a shared rope key of `qk_rope_head_dim`; 0 = ordinary GQA
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: Optional[YarnScaling] = None
    moe: MoEConfig = field(default_factory=MoEConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    # hybrid interleave: 1 attention layer per `attn_layer_period` layers,
    # at offset `attn_layer_offset`; the rest are SSM mixers. 0 = attention
    # everywhere (or SSM everywhere for arch_type == "ssm").
    attn_layer_period: int = 0
    attn_layer_offset: int = 0
    # encoder/decoder (audio): encoder is bidirectional over frame embeddings
    is_encoder_decoder: bool = False
    num_encoder_layers: int = 0
    # what the model consumes: "tokens" (int ids) or "embeds" (stubbed
    # modality frontend producing [B, T, d_model] features — audio carve-out)
    input_kind: str = "tokens"
    dtype: Any = jnp.bfloat16
    # activation remat policy for training: "none"|"block"
    remat: str = "block"
    # Pallas kernel dispatch for the serving hot paths (fused decode
    # attention over the compressed cache + flash prefill). None = auto:
    # kernels on TPU, the materialize/XLA oracle elsewhere. True forces
    # the kernel path (interpret mode off-TPU — slow, tests only); False
    # forces the oracle.
    use_kernels: Optional[bool] = None

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.qk_nope_head_dim + self.qk_rope_head_dim
                               if self.kv_lora_rank
                               else self.d_model // self.num_heads)

    # ---- derived ---------------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.moe.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Width of one latent row (MLA): latent + rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_store_dim(self) -> int:
        """Lanes a cached latent row takes: `latent_dim` rounded up to the
        TPU's 128-lane tile with zeros. The tile pads a narrower row to
        that width in memory anyway, and a row a whole number of tiles
        wide is laid out row by row, so a kernel reads it in place."""
        return -(-self.latent_dim // 128) * 128

    @property
    def lead_layers(self) -> int:
        """Leading dense layers, held apart from the layer pattern."""
        return self.moe.first_dense if self.is_moe else 0

    @property
    def is_ssm_only(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm.expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm.head_dim

    def layer_kind(self, idx: int) -> str:
        """Mixer kind of layer `idx`: "attn" or "ssm"."""
        if self.arch_type == "ssm":
            return "ssm"
        if self.attn_layer_period > 0:
            return (
                "attn"
                if idx % self.attn_layer_period == self.attn_layer_offset
                else "ssm"
            )
        return "attn"

    def ffn_kind(self, idx: int) -> str:
        """FFN kind of layer `idx`: "moe" or "dense". Layer period counts
        from 1 like Jamba (odd layers MoE when period==2), after the
        leading dense layers."""
        e = self.moe
        if (self.is_moe and idx >= e.first_dense
                and idx % e.layer_period == e.layer_period - 1):
            return "moe"
        return "dense"

    def num_attn_layers(self) -> int:
        return sum(1 for i in range(self.num_layers) if self.layer_kind(i) == "attn")

    def param_count(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        n = 0
        n += self.vocab_size * self.d_model                      # embed
        if not self.tie_embeddings:
            n += self.vocab_size * self.d_model                  # lm head
        for i in range(self.num_layers):
            n += 2 * self.d_model                                # norms
            if self.layer_kind(i) == "attn" and self.is_mla:
                r, h = self.kv_lora_rank, self.num_heads
                n += self.d_model * (h * self.head_dim + self.latent_dim)
                n += r + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                n += h * self.v_head_dim * self.d_model
            elif self.layer_kind(i) == "attn":
                hq = self.num_heads * self.head_dim
                hkv = self.num_kv_heads * self.head_dim
                n += self.d_model * (hq + 2 * hkv) + hq * self.d_model
                if self.qkv_bias:
                    n += hq + 2 * hkv
            else:
                d_in = self.d_inner
                conv_dim = d_in + 2 * self.ssm.n_groups * self.ssm.d_state
                n += self.d_model * (2 * d_in + 2 * self.ssm.n_groups * self.ssm.d_state + self.ssm_heads)
                n += conv_dim * self.ssm.d_conv
                n += 3 * self.ssm_heads                          # A, D, dt_bias
                n += d_in * self.d_model                         # out proj
                n += d_in                                        # gated norm
            if self.ffn_kind(i) == "moe":
                e = self.moe
                n += ((e.num_experts + e.num_shared) * 3 * self.d_model
                      * e.d_expert)
                n += self.d_model * e.num_experts                # router
            else:
                n += 3 * self.d_model * self.d_ff                # swiglu
        if self.is_encoder_decoder:
            # encoder blocks + decoder cross-attention
            hq = self.num_heads * self.head_dim
            enc = self.num_encoder_layers * (
                4 * self.d_model * hq + 3 * self.d_model * self.d_ff + 2 * self.d_model
            )
            xattn = self.num_layers * (
                self.d_model * (hq + 2 * self.num_kv_heads * self.head_dim)
                + hq * self.d_model
                + self.d_model
            )
            n += enc + xattn
        n += self.d_model                                        # final norm
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE top-k instead of all experts)."""
        if not self.is_moe:
            return self.param_count()
        n = self.param_count()
        e = self.moe
        n_moe_layers = sum(
            1 for i in range(self.num_layers) if self.ffn_kind(i) == "moe"
        )
        inactive = (e.num_experts - e.num_experts_per_tok)
        n -= n_moe_layers * inactive * 3 * self.d_model * e.d_expert
        return n

    def kv_bytes_per_token(self, bytes_per_elt: float = 2.0) -> float:
        """KV-cache bytes per token per sequence (the paper's core metric)."""
        b = 0.0
        for i in range(self.num_layers):
            if self.layer_kind(i) == "attn" and self.is_mla:
                b += self.latent_dim * bytes_per_elt
            elif self.layer_kind(i) == "attn":
                b += 2 * self.num_kv_heads * self.head_dim * bytes_per_elt
        return b

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A smoke-test-sized variant of the same family (<=2 layers, d<=512)."""
    kw: dict[str, Any] = dict(
        num_layers=2,
        d_model=min(cfg.d_model, 256),
        num_heads=min(cfg.num_heads, 4),
        num_kv_heads=min(cfg.num_kv_heads, 2),
        d_ff=min(cfg.d_ff, 512),
        vocab_size=min(cfg.vocab_size, 512),
        head_dim=64,
        dtype=jnp.float32,
        remat="none",
    )
    if cfg.is_moe:
        kw["moe"] = dataclasses.replace(
            cfg.moe,
            num_experts=min(cfg.moe.num_experts, 4),
            num_experts_per_tok=min(cfg.moe.num_experts_per_tok, 2),
            d_expert=min(cfg.moe.d_expert, 256),
            capacity_factor=float(min(cfg.moe.num_experts, 4)),  # drop-free
        )
    if cfg.arch_type in ("ssm", "hybrid"):
        kw["ssm"] = dataclasses.replace(
            cfg.ssm, d_state=min(cfg.ssm.d_state, 32), head_dim=32, chunk_size=32
        )
    if cfg.attn_layer_period > 0:
        kw["attn_layer_period"] = 2
        kw["attn_layer_offset"] = 1
    if cfg.is_encoder_decoder:
        kw["num_encoder_layers"] = 2
    if cfg.sliding_window:
        kw["sliding_window"] = 64
    kw.update(overrides)
    return cfg.replace(**kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "mamba2-130m",
    "mixtral-8x22b",
    "qwen2.5-32b",
    "minicpm-2b",
    "chameleon-34b",
    "command-r-plus-104b",
    "seamless-m4t-large-v2",
    "jamba-v0.1-52b",
    "kimi-k2-1t-a32b",
    "granite-8b",
    # the survey's own comparison model family
    "paper-llama-7b",
]

_MODULE_FOR: dict[str, str] = {
    "mamba2-130m": "mamba2_130m",
    "mixtral-8x22b": "mixtral_8x22b",
    "qwen2.5-32b": "qwen2_5_32b",
    "minicpm-2b": "minicpm_2b",
    "chameleon-34b": "chameleon_34b",
    "command-r-plus-104b": "command_r_plus_104b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "granite-8b": "granite_8b",
    "paper-llama-7b": "paper_llama_7b",
}


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULE_FOR:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULE_FOR)}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[arch]}")
    return mod.CONFIG


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
