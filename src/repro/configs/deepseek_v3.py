"""deepseek-v3 [moe]: 61 layers (the first 3 dense), multi-head latent
attention with YaRN rope, 256 routed experts (sigmoid scores, a correction
bias, 8 groups keeping 4, top-8) plus one shared expert.
[hf:deepseek-ai/DeepSeek-V3; arXiv:2412.19437]

The multi-token-prediction module is left out: greedy serving without
speculation never runs it. ``d_ff`` is the dense layers' width."""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v3",
    family="moe",
    n_layers=61,
    first_k_dense=3,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,
    vocab_size=129280,
    rope_theta=10_000.0,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=YaRNConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0,
                            mscale_all_dim=1.0),
    moe=MoEConfig(n_experts=256, top_k=8, d_ff=2048, n_shared_experts=1,
                  scoring="sigmoid", n_groups=8, topk_group=4,
                  score_bias=True, routed_scaling_factor=2.5,
                  aux_loss_weight=0.001),
    max_seq=163_840,
    source="hf:deepseek-ai/DeepSeek-V3; arXiv:2412.19437",
)

SMOKE = CONFIG.replace(
    name="deepseek-v3-smoke",
    n_layers=3, first_k_dense=1, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=128, vocab_size=512, max_seq=8192,
    mla=MLAConfig(q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=MoEConfig(n_experts=16, top_k=4, d_ff=32, n_shared_experts=1,
                  scoring="sigmoid", n_groups=4, topk_group=2,
                  score_bias=True, routed_scaling_factor=2.5,
                  aux_loss_weight=0.001),
)
