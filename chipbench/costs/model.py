"""Model FLOPs of the dense DARKFormer decoder, from a configuration
file's sizes. Multiply-adds count two; recomputation never counts.

PRF attention is counted in its linear form per token and layer: the
feature maps of the query heads and the KV groups (x -> M x -> W M x),
the readout qf.S and qf.z of each query head, and the state update
S += kf v^T, z += kf of each KV group.
"""
from __future__ import annotations


def sizes(cj: dict) -> dict:
    d = cj["hidden_size"]
    return {"d": d, "L": cj["num_hidden_layers"],
            "H": cj["num_attention_heads"],
            "G": cj["num_key_value_heads"], "dh": cj["head_dim"],
            "ff": cj["intermediate_size"], "V": cj["vocab_size"],
            "m": cj["num_random_features"],
            "r": cj.get("feature_rank") or cj["head_dim"]}


def layer_matmul_params(cj: dict) -> int:
    s = sizes(cj)
    attn = s["d"] * (s["H"] + 2 * s["G"]) * s["dh"] + s["H"] * s["dh"] * s["d"]
    mlp = 3 * s["d"] * s["ff"]
    return attn + mlp


def attention_flops_per_token(cj: dict) -> int:
    """PRF attention FLOPs of one token in one layer (linear form)."""
    s = sizes(cj)
    feat = 2 * s["dh"] * s["r"] + 2 * s["r"] * s["m"]
    readout = s["H"] * (2 * s["m"] * s["dh"] + 2 * s["m"])
    update = s["G"] * (2 * s["m"] * s["dh"] + s["m"])
    return (s["H"] + s["G"]) * feat + readout + update


def forward_flops_per_token(cj: dict) -> int:
    """One token through every layer, without the output head."""
    return sizes(cj)["L"] * (2 * layer_matmul_params(cj)
                             + attention_flops_per_token(cj))


def head_flops(cj: dict) -> int:
    """The output head for one position."""
    s = sizes(cj)
    return 2 * s["d"] * s["V"]


def train_flops_per_token(cj: dict) -> int:
    """Forward and backward: three times the forward, head on every
    position (the 6N rule plus attention)."""
    return 3 * (forward_flops_per_token(cj) + head_flops(cj))
