"""DeepSeek-V2-Lite [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434].

Multi-head latent attention (no q LoRA, a 512-wide KV latent, 128 + 64
q/k head widths with a shared 64-wide RoPE key, v 128) with YaRN RoPE
(factor 40 over an original 4096 positions), one leading dense layer
(d_ff 10944), then 26 DeepSeekMoE layers: 64 routed experts of 1408,
softmax top-6 without renormalisation, and 2 shared experts.  Untied head.
"""

from repro.configs.base import ATTN, LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=192,
    d_ff=10944,
    lead=(LayerSpec(ATTN, dense_ffn=True),),
    superblock=(LayerSpec(ATTN),),
    n_superblocks=26,
    ffn_kind="moe",
    n_experts=64,
    top_k=6,
    expert_d_ff=1408,
    n_shared_experts=2,
    norm_topk_prob=False,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=1e4,
    yarn_factor=40.0,
    yarn_orig_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    vocab_size=102400,
    tie_embeddings=False,
    norm_eps=1e-6,
)
