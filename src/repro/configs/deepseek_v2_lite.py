"""deepseek-v2-lite [moe] — MLA without a q LoRA (kv_lora=512), YaRN
rotary scaling, 2 shared + 64 routed experts top-6. [arXiv:2405.04434]

27L d_model=2048 16H (q/k 128+64, v 128) d_ff(expert)=1408 vocab=102400;
first layer dense (d_ff 10944).  The router's softmax weights are not
renormalised over the top 6, and the load-balance loss is taken per
sequence.  [hf:deepseek-ai/DeepSeek-V2-Lite config.json]
"""
from repro.configs.base import (
    ArchConfig,
    LayerSpec,
    MLAConfig,
    MoEConfig,
    RopeScaling,
)

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    citation="arXiv:2405.04434",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,                # dense-FFN hidden (layer 0)
    vocab_size=102_400,
    layer_pattern=(LayerSpec("mla", "moe"),),
    mla=MLAConfig(
        kv_lora_rank=512,
        q_lora_rank=None,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    moe=MoEConfig(
        n_experts=64,
        experts_per_token=6,
        n_shared_experts=2,
        d_expert=1408,
        first_k_dense=1,
        router_aux_coef=0.001,
        norm_topk_prob=False,
        seq_aux=True,
    ),
    rope_theta=10_000.0,
    rope_scaling=RopeScaling(
        factor=40.0,
        original_max_position_embeddings=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    norm="rmsnorm",
    ffn_activation="silu",
    tie_embeddings=False,
)
