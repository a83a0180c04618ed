"""FLOPs and bytes of a mixture-of-experts training step on one chip's
share of the experts, from a configuration file's sizes and the step's
routing counter: ``pairs``, the (token, choice) pairs routed to the
experts held here in one step, summed over layers. Multiply-adds count
two; recomputation never counts; bytes are bfloat16 (2 each).

The grouped matmuls: per pair, the gate and up projections (d -> f)
and the output projection (f -> d) forward; backward, each of the three
twice (the input's gradient and the weights'). Each of those nine
grouped matmuls reads its two operands and writes its output once: the
pairs' activations and the held experts' weights.
"""
from __future__ import annotations

BYTES = 2


def sizes(cj: dict) -> dict:
    return {"d": cj["hidden_size"], "f": cj["intermediate_size"],
            "L": cj["num_hidden_layers"], "E": cj["router_experts"],
            "held": cj["num_local_experts"], "V": cj["vocab_size"],
            "H": cj["num_attention_heads"], "G": cj["num_key_value_heads"],
            "dh": cj["head_dim"]}


def gmm_flops(cj: dict, pairs: float) -> float:
    """The grouped matmuls of one step, forward and backward."""
    s = sizes(cj)
    return 3 * (3 * 2 * s["d"] * s["f"]) * pairs


def gmm_bytes(cj: dict, pairs: float) -> float:
    """HBM bytes of the nine grouped matmuls of one step: each pair's
    rows of d and of f, read or written nine times in all, and the held
    experts' three matrices, nine times a layer."""
    s = sizes(cj)
    acts = 9 * pairs * (s["d"] + s["f"])
    weights = 9 * s["L"] * s["held"] * s["d"] * s["f"]
    return BYTES * (acts + weights)


def dense_params_per_token(cj: dict) -> int:
    """Matmul parameters every token meets: attention projections and
    the router in each layer, and the tied head."""
    s = sizes(cj)
    attn = s["d"] * (s["H"] + 2 * s["G"]) * s["dh"] \
        + s["H"] * s["dh"] * s["d"]
    return s["L"] * (attn + s["d"] * s["E"]) + s["d"] * s["V"]


def train_flops(cj: dict, tokens: int, pairs: float) -> float:
    """Model FLOPs of one step: 6 x the active parameters (the dense
    ones for every token, three expert matrices for every counted pair)
    plus three times the PRF attention's forward (``costs/model.py``)."""
    from chipbench import core
    s = sizes(cj)
    attn = core.load_module("costs", "model").attention_flops_per_token(cj)
    return (6 * (tokens * dense_params_per_token(cj)
                 + pairs * 3 * s["d"] * s["f"])
            + 3 * tokens * s["L"] * attn)
