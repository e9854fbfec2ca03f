"""moonlight-16b-a3b [moe] — deepseek-v3 architecture: latent attention (MLA),
sigmoid bias-corrected routing, one leading dense layer.

[hf:moonshotai/Moonlight-16B-A3B; config.json, model_type deepseek_v3]
27L d_model=2048 16H, vocab=163840, rms_norm_eps 1e-5, rope_theta 50000.
MLA: no query low-rank, kv_lora_rank 512 (RMS-normed latent),
qk_nope 128 + qk_rope 64 (the rope dims shared by every head), v 128; the
cache holds the 512 latent + 64 rope values a token.  Layer 0 has a dense
MLP of 11264; layers 1-26 route over 64 experts of 1408, top-6, on
sigmoid(score) + e_score_correction_bias (noaux_tc, one group), weighted by
the unbiased scores renormalised and times 2.446, plus 2 shared experts
(one SwiGLU of 2816).  Rope pairs are interleaved, as MLA's always are
here (HF's default ``rope_interleave``).  The layers differ in kind, so
they are held as a list (``scan_layers=False``).
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    head_dim=128,
    rope_theta=50000.0,
    norm_eps=1e-5,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    top_k=6,
    moe_d_ff=1408,
    n_shared_experts=2,
    shared_d_ff=2816,
    router_norm_topk=True,
    router_scoring="sigmoid",
    routed_scaling=2.446,
    first_k_dense=1,
    activation="silu",
    prefer_pure_dp=True,
    scan_layers=False,
)
