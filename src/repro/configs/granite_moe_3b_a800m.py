"""granite-moe-3b-a800m — fine-grained MoE, 40 experts top-8.

[hf:ibm-granite/granite-3.0-3b-a800m-base] 32L d_model=1536 24H (GQA
kv=8, d_head=64) per-expert d_ff=512, vocab=49155, with Granite's four
scalar multipliers (embedding x12, residual branches x0.22, attention
logits x1/64 in place of 1/sqrt(d), logits /6) and a router auxiliary
loss weight of 0.001.
"""
from repro.configs.base import DEFAULT_ATTN
from repro.models import ModelConfig, MoEConfig


# Sharding: 40 experts don't divide the 16-way model axis, and this
# geometry (d_model=1536, d_ff=512/expert) prefers d-over-data expert
# weights + classic megatron attention specs over the global rules.
_SHARDING = (
    (r"\['ffn'\]\['w_gate'\]$", (None, "data", "model")),
    (r"\['ffn'\]\['w_up'\]$",   (None, "data", "model")),
    (r"\['ffn'\]\['w_out'\]$",  (None, "model", "data")),
    (r"\['attn'\]\['w[qkv]'\]$", ("data", "model")),
    (r"\['attn'\]\['wo'\]$",    ("model", "data")),
)

_MULTIPLIERS = dict(embedding_multiplier=12.0, residual_multiplier=0.22,
                    attention_multiplier=0.015625, logits_scaling=6.0)


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-moe-3b-a800m", n_layers=32, d_model=1536, n_heads=24,
        n_kv=8, d_head=64, d_ff=512, vocab=49_155, attn=DEFAULT_ATTN,
        moe=MoEConfig(num_experts=40, top_k=8, d_ff=512,
                      aux_loss_weight=0.001),
        tie_embeddings=True, dtype="bfloat16",
        sharding_overrides=_SHARDING, **_MULTIPLIERS)


def reduced() -> ModelConfig:
    return ModelConfig(
        name="granite-moe-3b-a800m-smoke", n_layers=2, d_model=64,
        n_heads=4, n_kv=2, d_head=16, d_ff=32, vocab=256,
        attn=DEFAULT_ATTN.__class__(kind="darkformer", num_features=32),
        moe=MoEConfig(num_experts=8, top_k=2, d_ff=32,
                      aux_loss_weight=0.001),
        tie_embeddings=True, remat="none", **_MULTIPLIERS)
