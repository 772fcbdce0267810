"""Moonlight-16B-A3B (moonshot) as published
[hf:moonshotai/Moonlight-16B-A3B, config.json]: 27 layers of MLA (16
heads, no q compression, kv_lora 512, nope 128, rope 64, v 128); layer 0
has a dense SwiGLU FFN of 11264, layers 1-26 route each token to 6 of 64
SwiGLU experts of 1408 (sigmoid scores plus a selection bias, chosen
scores normalised, times 2.446) beside 2 shared experts; untied
embeddings, rope_theta 50000. The program's RMS epsilon is 1e-6 where the
source states 1e-5, and rotary rotates halves, not interleaved pairs."""
from repro.configs.base import ArchConfig, MoEConfig
from repro.models.attention import MLAConfig

CONFIG = ArchConfig(
    name="moonshot-v1-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab=163_840,
    mla=MLAConfig(q_lora=None, kv_lora=512, nope_dim=128, rope_dim=64,
                  v_dim=128, absorb=True),
    moe=MoEConfig(n_experts=64, top_k=6, every=1, d_expert=1408,
                  n_shared=2, scoring="sigmoid", norm_topk=True,
                  routed_scale=2.446),
    first_k_dense=1, tie_embeddings=False,
    ffn_kind="swiglu", rope_theta=50_000.0,
)
