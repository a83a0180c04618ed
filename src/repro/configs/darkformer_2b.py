"""darkformer-2b — the paper's own model: Gemma-2B with PRF attention.

Gemma-2B geometry [arXiv:2403.08295]: 18L d_model=2048 8H (MQA kv=1,
d_head=256) d_ff=16384 (GeGLU) vocab=256000, with the softmax kernel
replaced by the DARKFormer data-aware PRF (the paper's §6 setup).
"""
from repro.configs.base import DEFAULT_ATTN
from repro.models import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="darkformer-2b", n_layers=18, d_model=2048, n_heads=8,
        n_kv=1, d_head=256, d_ff=16_384, vocab=256_000, attn=DEFAULT_ATTN,
        mlp_kind="geglu", embedding_multiplier=2048 ** 0.5,
        tie_embeddings=True, dtype="bfloat16")


def reduced() -> ModelConfig:
    return ModelConfig(
        name="darkformer-2b-smoke", n_layers=3, d_model=64, n_heads=4,
        n_kv=1, d_head=16, d_ff=128, vocab=256, mlp_kind="geglu",
        attn=DEFAULT_ATTN.__class__(kind="darkformer", num_features=32),
        embedding_multiplier=64 ** 0.5, tie_embeddings=True, remat="none")
