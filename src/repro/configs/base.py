"""Model/config system: every assigned architecture is a ``ModelConfig``.

Families
--------
  dense   — decoder-only transformer (GQA/MHA, gated FFN)
  moe     — decoder-only with token-choice top-k MoE FFN; optionally
            DeepSeek-style: shared experts, leading dense layers
            (``first_k_dense``) and latent attention (``kv_lora_rank``)
  hybrid  — Jamba-style Mamba+attention interleave (1 attn per ``attn_every``)
            with MoE every ``moe_every`` layers
  ssm     — RWKV6 (attention-free; token-mix recurrence + channel-mix)
  encdec  — Whisper-style encoder-decoder (stub audio frontend)
  vlm     — LLaVA-style decoder backbone with stub patch-embedding prefix

Quantization: ``quant="ternary"`` runs the paper's BitNet b1.58 flow — all
weight projections are BitLinear (absmean ternary weights, absmax int8
activations); embeddings/head/norms stay high-precision (BitNet's own
convention). ``quant="bf16"`` is the unquantized baseline.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | hybrid | ssm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0           # 0 → d_model // n_heads
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25   # training dispatch only
    moe_group: int = 2048       # tokens per dispatch group (scanned)
    d_expert: int = 0           # routed expert width (0 → d_ff)
    n_shared_experts: int = 0   # always-on experts, one fused FFN
    first_k_dense: int = 0      # leading layers with a dense FFN of d_ff
    norm_topk_prob: bool = True  # renormalise the top-k gates to sum 1
    # --- multi-head latent attention (DeepSeek-V2); kv_lora_rank > 0
    # selects it: one cached latent row c ‖ k_rope per token ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- YaRN rotary scaling (rope_factor > 0 selects it) ---
    rope_factor: float = 0.0
    rope_original_max_pos: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # --- attention extras ---
    swa_window: int = 0         # 0 = full attention
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    # --- hybrid (Jamba) ---
    attn_every: int = 0         # 1 attention layer per this many (rest Mamba)
    moe_every: int = 0          # MoE FFN every this many layers (rest dense)
    mamba_d_state: int = 16
    mamba_expand: int = 2
    mamba_conv: int = 4
    # --- encdec (Whisper) ---
    n_encoder_layers: int = 0
    cross_ctx: int = 1500       # encoder frames visible to the decoder cache
    # --- vlm (LLaVA) ---
    n_img_tokens: int = 0       # stub patch embeddings prepended per sample
    # --- quantized flow / LOP ---
    quant: str = "ternary"      # ternary | bf16
    lop_block: int = 128        # KV candidate-block granularity (tokens)
    lop_keep: float = 0.125     # K/M — fraction of blocks kept by the screen
    use_lop: bool = True        # False for attention-free archs (rwkv6)
    # --- beyond-paper decode variants (DESIGN.md §Perf-variants) ---
    # Explicit kernel parameters of the fused decode path. ``None`` defers
    # to the legacy REPRO_GQA_SHARED_SELECT / REPRO_INT8_LOGITS env flags,
    # resolved ONCE at the engine entry (resolve_decode_flags) — never
    # inside traced inner functions.
    gqa_shared_select: bool | None = None  # one candidate set per KV head
    int8_logits: bool | None = None        # integer-domain QKᵀ in prefill
    # --- misc ---
    norm: str = "rmsnorm"       # rmsnorm | layernorm
    gated_ffn: bool = True      # silu-gated (False → gelu MLP, whisper)
    dtype: str = "float32"      # master param dtype (training)
    act_dtype: str = "bfloat16"  # activation/compute dtype (training)

    # ---------------- derived ----------------
    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.hd

    @property
    def mla(self) -> bool:
        """Latent attention: the cache holds one latent row per token."""
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Width of the cached MLA row c ‖ k_rope."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def mla_q_dim(self) -> int:
        return self.n_heads * (self.qk_nope_head_dim + self.qk_rope_head_dim)

    @property
    def expert_width(self) -> int:
        return self.d_expert or self.d_ff

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.hd

    @property
    def vocab_padded(self) -> int:
        """Vocab padded so TP over the model axis always divides."""
        return -(-self.vocab // 256) * 256

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    def is_attn_layer(self, i: int) -> bool:
        if self.family == "ssm":
            return False
        if self.family == "hybrid":
            # Jamba: one attention layer per `attn_every` block (offset mid-block)
            return i % self.attn_every == self.attn_every // 2
        return True

    def is_moe_layer(self, i: int) -> bool:
        if self.n_experts == 0 or i < self.first_k_dense:
            return False
        if self.moe_every:
            return i % self.moe_every == 1
        return True

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


def resolve_decode_flags(cfg: "ModelConfig") -> "ModelConfig":
    """Pin the beyond-paper decode variants to concrete booleans.

    Config fields are the source of truth; a ``None`` field falls back to
    the matching environment flag for backwards compatibility. Called once
    at the engine entry points (``prefill`` / ``serve_step`` /
    ``sp_decode_attention``) so no traced inner function ever consults
    ``os.environ`` — the flags flow through the code as explicit
    ``ModelConfig`` state and land in the fused decode kernel as static
    parameters.
    """
    if cfg.gqa_shared_select is not None and cfg.int8_logits is not None:
        return cfg
    import os
    shared = cfg.gqa_shared_select
    int8l = cfg.int8_logits
    if shared is None:
        shared = os.environ.get("REPRO_GQA_SHARED_SELECT") == "1"
    if int8l is None:
        int8l = os.environ.get("REPRO_INT8_LOGITS") == "1"
    return cfg.replace(gqa_shared_select=shared, int8_logits=int8l)


# ---------------------------------------------------------------------------
# Input shapes (assigned shape set — seq_len × global_batch)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# smoke-scale variants of the same shapes (CPU tests)
SMOKE_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 64, 2, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 128, 2, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 128, 2, "decode"),
    "long_500k": ShapeConfig("long_500k", 256, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """(runs?, reason-if-skipped) per the brief's skip rules."""
    if shape.name == "long_500k":
        if cfg.family in ("ssm", "hybrid"):
            return True, ""
        return False, ("long_500k needs sub-quadratic attention; "
                       f"{cfg.name} is full-attention — skipped per brief "
                       "(noted in DESIGN.md §Arch-applicability)")
    return True, ""


def text_len(cfg: ModelConfig, seq_len: int, kind: str) -> int:
    """Token length of the *decoder text stream* for a given cell seq_len."""
    if cfg.family == "encdec":
        # seq_len counts audio frames; decoder text is seq_len/4 (DESIGN §6)
        return max(seq_len // 4, 8)
    if cfg.family == "vlm" and kind in ("train", "prefill"):
        return max(seq_len - cfg.n_img_tokens, 8)
    return seq_len


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    Returns a dict matching the kwargs of the corresponding step function
    (train_step / prefill / serve_step). No device allocation.
    """
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        t = text_len(cfg, s, "train")
        specs = {"tokens": sds((b, t), jnp.int32),
                 "labels": sds((b, t), jnp.int32)}
        if cfg.family == "encdec":
            specs["frames"] = sds((b, s, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            specs["patches"] = sds((b, cfg.n_img_tokens, cfg.d_model),
                                   jnp.bfloat16)
        return specs
    if shape.kind == "prefill":
        t = text_len(cfg, s, "prefill")
        specs = {"tokens": sds((b, t), jnp.int32)}
        if cfg.family == "encdec":
            specs["frames"] = sds((b, s, cfg.d_model), jnp.bfloat16)
        if cfg.family == "vlm":
            specs["patches"] = sds((b, cfg.n_img_tokens, cfg.d_model),
                                   jnp.bfloat16)
        return specs
    # decode: one new token against a cache of seq_len (cache passed separately)
    return {"tokens": sds((b, 1), jnp.int32)}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, ModelConfig] = {}
_LOADED = False


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _load_all()
    return _REGISTRY[name]


def all_configs() -> dict[str, ModelConfig]:
    _load_all()
    return dict(_REGISTRY)


def _load_all():
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # import side-effect registers each arch
    from repro.configs import (bitnet_3b, deepseek_v2_lite,  # noqa: F401
                               granite_moe_1b_a400m,
                               jamba_1_5_large_398b, llava_next_34b,
                               mistral_nemo_12b, mixtral_8x22b, qwen1_5_110b,
                               qwen1_5_32b, rwkv6_1_6b, stablelm_1_6b,
                               whisper_small)


ASSIGNED = [
    "mixtral-8x22b", "granite-moe-1b-a400m", "whisper-small",
    "jamba-1.5-large-398b", "llava-next-34b", "qwen1.5-32b", "stablelm-1.6b",
    "mistral-nemo-12b", "qwen1.5-110b", "rwkv6-1.6b",
]
