"""moonlight-16b-a3b — DeepSeek-V3 block: MLA with a direct query, one
leading dense layer, then MoE layers of 64 sigmoid-routed experts.

[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]
27 layers at hidden 2048; layer 0 dense (SwiGLU 11,264), layers 1-26
MoE: 64 routed SwiGLU experts of width 1,408, 6 per token, chosen by
``noaux_tc`` (sigmoid scores plus ``e_score_correction_bias``,
``n_group`` = ``topk_group`` = 1, normalised top-k, routed scaling
2.446), and 2 shared experts (one SwiGLU of width 2 x 1,408).  MLA
without query compression (``q_lora_rank`` null): kv_lora_rank 512, 16
heads, qk_nope 128, qk_rope 64, v 128.  RMSNorm eps 1e-5, rope theta
50,000 unscaled, context 8,192, vocabulary 163,840 untied.

This is the published model, every chip's experts held.  A federation
states its chip's share (depth, experts held, vocabulary slice) through
``FederationSpec`` (``model.published``; docs/lm_federation.md).
"""
from repro.configs.base import MOE, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    kind=MOE,
    citation="hf:moonshotai/Moonlight-16B-A3B",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,                 # qk_nope_head_dim = v_head_dim
    d_ff=1408,                    # moe_intermediate_size
    vocab_size=163840,
    max_seq_len=8192,
    rope_theta=50000.0,
    norm_eps=1e-5,
    tie_embeddings=False,
    activation="swiglu",
    use_mla=True,
    mla_kv_lora_rank=512,
    mla_q_lora_rank=0,            # q_lora_rank null: w_q is direct
    mla_rope_head_dim=64,
    first_k_dense=1,
    dense_d_ff=11264,             # intermediate_size
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2,
                  routing="noaux_tc", routed_scaling_factor=2.446),
    remat_layers=True,
)
