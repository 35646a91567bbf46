"""moonlight-16b-a3b [moe]: DeepSeek-V3's block at 16B total / 3B active
[hf:moonshotai/Moonlight-16B-A3B, config.json, model_type deepseek_v3].

Multi-head latent attention with no q LoRA (16 heads; a query/key head
is 128 + 64 RoPE lanes, a value head 128; the K/V latent is 512 wide),
layer 0 a dense SwiGLU of width 11,264, layers 1-26 an MoE of 64 routed
experts of width 1,408, top-6 by sigmoid scores plus a correction bias
(``noaux_tc``; one group, so group-limited routing does nothing), gates
normalised over the six and scaled by 2.446, and 2 shared experts run as
one SwiGLU of width 2,816.  RoPE theta 50,000 with no scaling; untied
head.  The integer path runs it through ``int_prefill`` only: decoding
MLA wants a latent paged cache (``inttransformer.MLADecodeError``)."""
from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe", num_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=11264, vocab=163840, head_dim=192,
    rope_theta=50000.0, activation="swiglu", norm="rmsnorm",
    norm_eps=1e-5,
    n_experts=64, top_k=6, n_shared_experts=2, moe_d_ff=1408,
    moe_every=1, first_k_dense=1, router="sigmoid", routed_scaling=2.446,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
)
