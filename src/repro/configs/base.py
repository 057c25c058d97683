"""Model / shape configuration dataclasses.

Every assigned architecture is a ModelConfig instance in its own file
under repro/configs/; `reduced()` derives the small same-family config
used by the CPU smoke tests. The four assigned input shapes are
ShapeConfig instances (train lowers train_step; prefill lowers the
prefill forward; decode/long lower serve_step with a full KV cache).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 128
    # attention
    attn_type: str = "gqa"      # gqa | mla | none
    rope_theta: float = 1e4
    # MLA (deepseek-family)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    first_k_dense: int = 0      # leading dense layers (deepseek-style)
    # router (deepseek-style group-limited top-k; core/moe.route)
    n_group: int = 1            # expert groups
    topk_group: int = 1         # groups kept per token
    routed_scaling_factor: float = 1.0
    scoring_func: str = "softmax"   # softmax | sigmoid
    norm_topk_prob: bool = False    # normalise the selected scores to sum 1
    moe_every: int = 1          # MoE on layers where (idx % moe_every)==moe_offset
    moe_offset: int = 0
    capacity_factor: float = 1.25
    # SSM / hybrid
    ssm_type: str = ""          # rwkv6 | mamba
    attn_period: int = 0        # jamba: one attention layer per period
    attn_period_offset: int = 0
    d_state: int = 16
    d_conv: int = 4
    ssm_expand: int = 2
    # encoder-decoder
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    encoder_seq: int = 1024     # frontend frames fed to the encoder
    # multimodal stub frontends
    modality_prefix: int = 0    # precomputed embedding tokens per sample
    # misc
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    mtp: bool = False           # deepseek multi-token prediction
    scale_emb: float = 1.0      # minicpm embedding scale
    scale_depth: float = 0.0    # minicpm residual scale (0 = off)
    scale_logits: float = 1.0   # minicpm: 1 / (d_model / dim_model_base)
    # numerics
    param_dtype: str = "float32"     # master/param dtype
    compute_dtype: str = "bfloat16"
    # FSDP reach: shard params over data only, or pod+data (huge MoE)
    fsdp_over_pod: bool = False
    # the paper's technique as a serving backend
    analog_mvm: bool = False
    analog_tech: str = "PCM"
    # capability markers
    supports_long_context: bool = False   # sub-quadratic decode state
    # implementation detail: embedding/head tables are padded so the
    # vocab dim shards on the 16-wide model axis (Megatron-style vocab
    # padding; logits for pad entries are masked to -inf). The logical
    # vocab (config above) is unchanged.
    pad_vocab_to: int = 256

    @property
    def vocab_padded(self) -> int:
        m = self.pad_vocab_to
        return ((self.vocab + m - 1) // m) * m

    @property
    def moe_enabled(self) -> bool:
        return self.n_experts > 0

    def is_moe_layer(self, idx: int) -> bool:
        if not self.moe_enabled or idx < self.first_k_dense:
            return False
        return (idx % self.moe_every) == self.moe_offset

    def is_attn_layer(self, idx: int) -> bool:
        if self.ssm_type == "rwkv6":
            return False
        if self.attn_period:
            return (idx % self.attn_period) == self.attn_period_offset
        return True

    def n_params(self) -> int:
        """Approximate parameter count (embedding + blocks + head)."""
        e, f, v = self.d_model, self.d_ff, self.vocab
        h, kv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        total = v * e  # embedding
        if not self.tie_embeddings:
            total += v * e
        enc_layers = self.n_encoder_layers if self.is_encoder_decoder else 0
        for idx in range(self.n_layers + enc_layers):
            if self.is_attn_layer(idx % max(self.n_layers, 1)):
                if self.attn_type == "mla":
                    qk_head = self.qk_nope_head_dim + self.qk_rope_head_dim
                    total += e * (self.q_lora_rank or e)
                    if self.q_lora_rank:
                        total += self.q_lora_rank * h * qk_head
                    total += e * (self.kv_lora_rank + self.qk_rope_head_dim)
                    total += self.kv_lora_rank * h * (
                        self.qk_nope_head_dim + self.v_head_dim
                    )
                    total += h * self.v_head_dim * e
                else:
                    total += e * h * hd + 2 * e * kv * hd + h * hd * e
            elif self.ssm_type == "mamba" or (
                self.attn_period and not self.is_attn_layer(idx)
            ):
                di = self.ssm_expand * e
                total += e * 2 * di + di * self.d_conv + di * (
                    2 * self.d_state + math.ceil(e / 16)
                ) + di * e
            elif self.ssm_type == "rwkv6":
                total += 5 * e * e  # r,k,v,g,o mixes (approx)
            if self.is_moe_layer(idx):
                total += self.n_experts * 3 * e * self.moe_d_ff
                total += self.n_shared_experts * 3 * e * self.moe_d_ff
                total += e * self.n_experts  # router
            else:
                total += 3 * e * f
            total += 2 * e  # norms
        return total

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: routed top-k + shared)."""
        if not self.moe_enabled:
            return self.n_params()
        e = self.d_model
        total = self.n_params()
        n_moe_layers = sum(
            self.is_moe_layer(i) for i in range(self.n_layers)
        )
        inactive = (self.n_experts - self.experts_per_token)
        total -= n_moe_layers * inactive * 3 * e * self.moe_d_ff
        return total

    def reduced(self) -> "ModelConfig":
        """Tiny same-family config for CPU smoke tests."""
        def shrink(x, lo, fac):
            return max(lo, x // fac)

        return dataclasses.replace(
            self,
            n_layers=min(self.n_layers, 4 if not self.attn_period else self.attn_period),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            head_dim=32,
            d_ff=256,
            vocab=512,
            q_lora_rank=64 if self.q_lora_rank else 0,
            kv_lora_rank=32 if self.kv_lora_rank else 0,
            qk_nope_head_dim=32 if self.attn_type == "mla" else self.qk_nope_head_dim,
            qk_rope_head_dim=16 if self.attn_type == "mla" else self.qk_rope_head_dim,
            v_head_dim=32 if self.attn_type == "mla" else self.v_head_dim,
            n_experts=min(self.n_experts, 8) if self.n_experts else 0,
            n_shared_experts=min(self.n_shared_experts, 1),
            experts_per_token=min(self.experts_per_token, 2) if self.n_experts else 0,
            n_group=min(self.n_group, 2),
            topk_group=min(self.topk_group, 1),
            moe_d_ff=64 if self.moe_d_ff else 0,
            first_k_dense=min(self.first_k_dense, 1),
            n_encoder_layers=min(self.n_encoder_layers, 2),
            encoder_seq=32,
            modality_prefix=min(self.modality_prefix, 8),
            d_state=min(self.d_state, 8),
            param_dtype="float32",
            compute_dtype="float32",
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    def reduced(self) -> "ShapeConfig":
        return dataclasses.replace(
            self, seq_len=min(self.seq_len, 64), global_batch=min(self.global_batch, 2)
        )


SHAPES: "dict[str, ShapeConfig]" = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}
