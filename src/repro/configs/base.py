"""Model/shape configuration system.

Every assigned architecture is a `ModelConfig`; the four assigned input
shapes are `ShapeConfig`s. `reduced()` derives a CPU-smoke-test-sized config
of the same family (same block structure, tiny dims).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input shape."""
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


# The assigned shape grid (identical for every LM-family arch).
SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads

    # --- attention options ---
    qkv_bias: bool = False
    sliding_window: int = 0          # >0 -> SWA (mixtral)
    rope_theta: float = 500000.0
    tie_embeddings: bool = False

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_dense_ff: int = 0            # arctic: parallel dense residual FFN
    capacity_factor: float = 1.25    # training only: inference is dropless
    moe_d_ff: int = 0                # routed expert width (0 -> d_ff)
    n_shared_experts: int = 0        # experts every token passes through
    first_k_dense: int = 0           # leading dense layers before the MoE
    norm_topk: bool = True           # renormalize the top-k gates
    experts_held: int = 0            # routed experts held here, from
                                     # expert 0 (0 -> all n_experts)

    # --- latent attention (MLA, deepseek-v2) ---
    attn_kind: str = "gqa"           # gqa | mla
    kv_lora_rank: int = 0            # latent c_kv width
    qk_nope_dim: int = 0             # per-head query/key part without rope
    qk_rope_dim: int = 0             # per-head rope part (one shared key)
    v_head_dim: int = 0
    rope_scaling: tuple = ()         # YaRN: (key, value) pairs of HF's
                                     # rope_scaling (a dict is accepted)

    # --- SSM / hybrid (zamba2) ---
    ssm_state: int = 0               # Mamba2 d_state
    ssm_expand: int = 2
    ssm_headdim: int = 64
    conv_kernel: int = 4
    attn_every: int = 0              # zamba2: shared attn+MLP block period

    # --- xLSTM ---
    slstm_every: int = 0             # one sLSTM per group of this many blocks
    mlstm_proj_factor: float = 2.0

    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    enc_frames: int = 0              # stub frontend: precomputed frame embeds

    # --- vlm (internvl2) ---
    n_patches: int = 0               # stub frontend: precomputed patch embeds

    # --- numerics / norm ---
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    act: str = "silu"                # silu | gelu
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    # --- training-time knobs ---
    remat: str = "full"              # none | dots | full
    attn_seqpar: bool = True         # context-parallel flash when heads
                                     # don't divide the model axis (§Perf)
    kv_dtype: str = "bfloat16"       # "int8" -> quantized KV cache with
                                     # per-token-per-head scales (§Perf)
    optimizer: str = "adamw"         # adamw | adafactor
    schedule: str = "cosine"         # cosine | wsd

    def __post_init__(self):
        if self.first_k_dense and self.attn_kind != "mla":
            raise ValueError("leading dense layers decode through a latent "
                             "cache only (attn_kind='mla')")
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        else:
            object.__setattr__(self, "rope_scaling",
                               tuple(tuple(kv) for kv in self.rope_scaling))

    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def n_held(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.experts_held or self.n_experts

    def cache_row_elems(self) -> int:
        """Elements of one cache row (one position, one layer): the
        latent c_kv and the shared rope key under MLA, else K and V."""
        if self.attn_kind == "mla":
            return self.kv_lora_rank + self.qk_rope_dim
        return 2 * self.n_kv_heads * self.hd()

    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def subquadratic(self) -> bool:
        """Can this arch run long_500k decode with bounded memory?"""
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    def shapes(self):
        """The live (non-skipped) shape list for this arch."""
        out = [SHAPES["train_4k"], SHAPES["prefill_32k"], SHAPES["decode_32k"]]
        if self.subquadratic:
            out.append(SHAPES["long_500k"])
        return out

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests. Latent attention,
        shared experts, the held share (an eighth of 16 experts) and the
        leading dense layers are kept at tiny widths."""
        mla = self.attn_kind == "mla"
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=max(2, min(4, self.n_layers)),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 4) if self.n_kv_heads < self.n_heads else 4,
            head_dim=16,
            d_ff=128 if self.d_ff else 0,
            vocab_size=256,
            n_experts=min(self.n_experts,
                          16 if self.experts_held or self.top_k > 4 else 4),
            experts_held=2 if self.experts_held else 0,
            moe_d_ff=32 if self.moe_d_ff else 0,
            first_k_dense=min(self.first_k_dense, 1),
            kv_lora_rank=32 if mla else 0,
            qk_nope_dim=16 if mla else 0,
            qk_rope_dim=8 if mla else 0,
            v_head_dim=16 if mla else 0,
            moe_dense_ff=64 if self.moe_dense_ff else 0,
            ssm_state=16 if self.ssm_state else 0,
            ssm_headdim=16,
            sliding_window=32 if self.sliding_window else 0,
            attn_every=2 if self.attn_every else 0,
            slstm_every=2 if self.slstm_every else 0,
            n_enc_layers=2 if self.n_enc_layers else 0,
            enc_frames=8 if self.enc_frames else 0,
            n_patches=4 if self.n_patches else 0,
            remat="none",
        )

    # ---- parameter counting (used by roofline MODEL_FLOPS) ----
    def param_count(self) -> int:
        from repro.models.model import Model
        return Model(self).param_count()

    def active_param_count(self) -> int:
        from repro.models.model import Model
        return Model(self).param_count(active_only=True)
