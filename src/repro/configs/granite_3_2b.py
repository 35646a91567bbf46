"""granite-3-2b [dense]: GQA, tied embeddings
[hf:ibm-granite/granite-3.0-2b-base].  vocab 49155 padded to 49168 for
16-way vocab sharding (DESIGN.md §7).  The four scalar multipliers are
the published ones (config.json: embedding_multiplier,
attention_multiplier, residual_multiplier, logits_scaling)."""
from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    name="granite-3-2b", family="dense", num_layers=40, d_model=2048,
    n_heads=32, n_kv_heads=8, d_ff=8192, vocab=49155, head_dim=64,
    tie_embeddings=True, activation="swiglu", norm="rmsnorm",
    rope_theta=10000.0,
    embedding_multiplier=12.0, attention_multiplier=0.015625,
    residual_multiplier=0.22, logits_scaling=8.0,
)
