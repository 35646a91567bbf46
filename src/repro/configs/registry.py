"""--arch <id> registry: the 10 assigned architectures, the paper's own
evaluation models (RoBERTa-base/large, DeiT-S) and Moonlight-16B-A3B
(MLA and sigmoid-routed experts, prefill only)."""
from repro.configs import (codeqwen1_5_7b, deit_s, granite_3_2b,
                           h2o_danube_3_4b, jamba_v0_1_52b, llama3_8b,
                           llama3_2_vision_90b, mamba2_130m,
                           moonlight_16b_a3b, qwen2_moe_a2_7b,
                           qwen3_moe_235b_a22b, roberta_base,
                           roberta_large, seamless_m4t_large_v2)

ARCHS = {m.CONFIG.name: m.CONFIG for m in (
    h2o_danube_3_4b, llama3_8b, codeqwen1_5_7b, granite_3_2b,
    seamless_m4t_large_v2, llama3_2_vision_90b, qwen3_moe_235b_a22b,
    qwen2_moe_a2_7b, mamba2_130m, jamba_v0_1_52b,
    roberta_base, roberta_large, deit_s, moonlight_16b_a3b,
)}

ASSIGNED = [
    "h2o-danube-3-4b", "llama3-8b", "codeqwen1.5-7b", "granite-3-2b",
    "seamless-m4t-large-v2", "llama-3.2-vision-90b", "qwen3-moe-235b-a22b",
    "qwen2-moe-a2.7b", "mamba2-130m", "jamba-v0.1-52b",
]

# long_500k applicability (DESIGN.md §6): sub-quadratic archs only
LONG_OK = {"h2o-danube-3-4b", "mamba2-130m", "jamba-v0.1-52b"}


def get_config(name: str):
    if name not in ARCHS:
        raise KeyError(f"unknown arch '{name}'; known: {sorted(ARCHS)}")
    return ARCHS[name]
