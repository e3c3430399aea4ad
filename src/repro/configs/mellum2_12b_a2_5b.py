"""mellum2-12b-a2.5b [moe] — JetBrains Mellum2-12B-A2.5B-Instruct
(huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, config.json).

GQA 32/4 heads of 128; every layer sparse: 64 experts of width 896, 8 per
token, renormalised over the 8, no shared expert.  Three 1 024-token
sliding-window layers to each full-attention layer; rope theta 5e5 on all
of them, YaRN-scaled (factor 16 over 8 192 positions) on the full ones.
"""
from repro.configs.base import ModelConfig

_PERIOD = ("sliding_attention",) * 3 + ("full_attention",)

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,
    vocab_size=98_304,
    sliding_window=1024,
    layer_types=_PERIOD * 7,
    rope_theta=500_000.0,
    yarn_factor=16.0,
    yarn_original_max_position=8192,
    n_experts=64,
    top_k=8,
    act="silu",
    norm_eps=1e-6,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json",
)

SMOKE = CONFIG.reduced(
    n_layers=8, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=32,
    vocab_size=512, sliding_window=8, layer_types=_PERIOD * 2,
    yarn_original_max_position=64, n_experts=8, top_k=2,
)
