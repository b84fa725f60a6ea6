"""olmoe-1b-7b [moe]: 16L d_model=2048 16H (MHA) d_ff=1024 per expert
vocab=50304, dropless MoE of 64 SwiGLU experts, top-8.
[arXiv:2409.02060; hf:allenai/OLMoE-1B-7B-0924]

~1.3B active of 6.92B parameters.  RMSNorm (eps 1e-5) with a scale; QK-norm
is an RMSNorm over the whole q and the whole k projection, before RoPE;
the top-8 router weights are not renormalised (``norm_topk_prob`` false);
untied output head.
"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="olmoe-1b-7b",
    family="moe",
    n_layers=16,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1024,
    vocab_size=50304,
    n_experts=64,
    top_k=8,
    norm_topk_prob=False,
    qk_norm=True,
    qk_norm_width="full",
    rope_theta=10_000.0,
    norm_kind="rmsnorm",
    norm_eps=1e-5,
    mlp_kind="swiglu",
    tie_embeddings=False,
    block_pattern=("attn",),
)
