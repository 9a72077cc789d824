"""DeepSeek-V2-Lite 16B [arXiv:2405.04434] — MLA (kv_lora=512) +
64 routed experts top-6 with 2 shared experts.

Follows the published config
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json):
27 layers at d_model 2048; MLA with no q LoRA (kv_lora_rank 512,
qk_nope/rope/v head dims 128/64/128, an RMSNorm on the latent); YaRN RoPE
(factor 40 over an original 4096 positions, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707); ``first_k_dense_replace`` 1, so layer 0
holds a dense SwiGLU MLP of width 10,944 (``d_ff``) and the 26 others a
softmax router over 64 experts of width 1408, top-6 greedy, gates not
renormalised (``norm_topk_prob`` false; ``routed_scaling_factor`` 1), plus
2 shared experts as one MLP of width 2816; vocabulary 102,400, untied head.

Assignment note: the primary spec line says "MoE 64e top-6"; the bracket
mentions "160 routed" which matches DeepSeek-V2 (236B), not Lite. We follow
the primary spec (64 routed, top-6, 2 shared), which is the real V2-Lite.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=10944,                # the leading dense layer's MLP
    vocab_size=102400,
    attention="mla",
    rope_theta=10000.0,
    yarn=YarnConfig(factor=40.0, original_max_position=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    norm="rmsnorm",
    activation="swiglu",
    first_k_dense=1,
    mla=MLAConfig(
        kv_lora_rank=512,
        q_lora_rank=0,          # Lite uses full-rank Q
        rope_head_dim=64,
        nope_head_dim=128,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_ff_expert=1408,
        num_shared_experts=2,
        norm_topk_prob=False,
        max_copies=4,
    ),
    source="arXiv:2405.04434",
)
