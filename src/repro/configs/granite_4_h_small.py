"""IBM Granite-4.0-H-Small, 32B-A9B [hf: ibm-granite/granite-4.0-h-small,
config.json, model_type granitemoehybrid].

Hybrid: 40 layers, each a token mixer and a MoE FFN, both pre-RMSNorm and
residual. 36 mixers are Mamba-2 (128 heads of 64, d_state 128, one group,
expand 2, conv 4 with bias); 4 are GQA attention (32 q / 8 kv heads of 128)
with no position embedding, at layers 5, 15, 25, 35: one per period of 10
at offset 5, ``layer_kind``'s rule for ``attn_every=10``. Every layer has
72 routed SwiGLU experts 768 wide, top-10, and one shared SwiGLU expert
1,536 wide. muP multipliers: embeddings x12, residual branches x0.22,
softmax scale 1/128 (attention_multiplier), logits /16 (logits_scaling).
Tied embeddings, vocab 100,352, rms_norm_eps 1e-5.
"""
from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite_4_h_small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768, vocab=100_352,
    moe_experts=72, moe_top_k=10, moe_shared_ff=1536,
    attn_every=10, ssm_state=128, ssm_expand=2, ssm_head_dim=64,
    ssm_conv=4, ssm_conv_bias=True,
    pos_emb="nope", attn_scale=0.0078125, rms_eps=1e-5,
    embed_mult=12.0, residual_mult=0.22, logits_div=16.0,
    tie_embeddings=True,
)

SMOKE = ModelConfig(
    name="granite_smoke", family="hybrid",
    n_layers=4, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
    d_ff=64, vocab=512,
    moe_experts=8, moe_top_k=3, moe_shared_ff=96,
    attn_every=4, ssm_state=16, ssm_expand=2, ssm_head_dim=32,
    ssm_conv=4, ssm_conv_bias=True,
    pos_emb="nope", attn_scale=0.05, rms_eps=1e-5,
    embed_mult=12.0, residual_mult=0.22, logits_div=16.0,
    tie_embeddings=True,
)
