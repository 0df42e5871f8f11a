"""mellum2-12b-a2.5b [moe] — 28L d_model=2304 32H (GQA kv=4) head_dim=128,
every layer sparse: 64 experts top-8 (renormalised) of width 896, no shared
expert; sliding-window (1024) attention on three layers of four, full
attention with YaRN (factor 16 over 8192 positions) on the fourth; rope
theta 500000, vocab 98304, untied head
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

``qk_norm``: config.json has no key for it; the file carries Qwen3-MoE's
keys (``norm_topk_prob``, ``moe_intermediate_size``, ``max_window_layers``)
and that block applies per-head RMSNorm to q and k, which this runs.
"""
from repro.models.config import ModelConfig

# full-attention layers: (factor, original positions, beta_fast, beta_slow,
# attention_factor) of config.json's rope_parameters.full_attention
YARN = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
PATTERN = (("attn_local", "moe"),) * 3 + (("attn", "moe"),)

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    vocab_size=98304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=0,
    qk_norm=True,
    rope=True,
    rope_theta=500_000.0,
    yarn=YARN,
    tie_embeddings=False,
    pattern=PATTERN,
    window=1024,
    n_experts=64,
    top_k=8,
    moe_d_ff=896,
)

SMOKE = ModelConfig(
    name="mellum2-smoke",
    family="moe",
    n_layers=4,
    d_model=64,
    vocab_size=256,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    d_ff=0,
    qk_norm=True,
    rope_theta=500_000.0,
    yarn=YARN,
    tie_embeddings=False,
    pattern=PATTERN,
    window=4,
    n_experts=8,
    top_k=2,
    moe_d_ff=32,
    capacity_factor=8.0,   # no-drop at smoke scale: decode/prefill/forward agree exactly
    dtype="float32",
)
