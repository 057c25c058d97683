"""deepseek-v3-671b — MLA + 1 shared/256 routed top-8 MoE + MTP
[arXiv:2412.19437; hf]. First 3 layers dense (d_ff 18432); MoE expert
d_ff 2048; MLA q_lora 1536 / kv_lora 512 (+64 rope). Router: sigmoid
scores, 8 groups of 32 experts ranked by their top-2 biased scores, the
best 4 kept, top-8 weights normalised and scaled by 2.5 (noaux_tc)."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,        # MLA: heads share one compressed KV (MQA-like cache)
    head_dim=128,
    d_ff=18432,            # dense layers
    vocab=129280,
    attn_type="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=256,
    n_shared_experts=1,
    experts_per_token=8,
    moe_d_ff=2048,
    n_group=8,
    topk_group=4,
    routed_scaling_factor=2.5,
    scoring_func="sigmoid",
    norm_topk_prob=True,
    first_k_dense=3,
    mtp=True,
    param_dtype="bfloat16",   # 671B: bf16 params + 8-bit optim state
    fsdp_over_pod=True,
)
