"""From a configuration file to the program's model config and to the
reference's sizes."""
from __future__ import annotations

import dataclasses

from chipbench.core import BenchError

# configuration-file key -> program ModelConfig field
_CHECK = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "tie_word_embeddings": "tie_embeddings",
          "rope_theta": "rope_theta"}


def program_config(cj: dict):
    """The program's ModelConfig for configuration file ``cj``, checked
    against every size the file states."""
    from repro import configs as cfgs
    prog = cj["program"]
    cfg = cfgs.get_config(prog["arch"], reduced=prog.get("reduced", False),
                          **prog.get("overrides", {}))
    if "remat" in prog:
        cfg = dataclasses.replace(cfg, remat=prog["remat"])
    if "attention_eps" in prog:
        cfg = dataclasses.replace(cfg, attn=dataclasses.replace(
            cfg.attn, eps=prog["attention_eps"]))
    for key, field in _CHECK.items():
        if getattr(cfg, field) != cj[key]:
            raise BenchError(f"{cj['name']}: program {field}="
                             f"{getattr(cfg, field)!r}, file {key}="
                             f"{cj[key]!r}")
    checks = {"attention_kernel": cfg.attn.kind,
              "num_random_features": cfg.attn.num_features,
              "torch_dtype": cfg.dtype, "hidden_act":
              "silu" if cfg.mlp_kind == "swiglu" else cfg.mlp_kind}
    for key, val in checks.items():
        if cj[key] != val:
            raise BenchError(f"{cj['name']}: program {key}={val!r}, "
                             f"file {cj[key]!r}")
    return cfg


def ref_dims(cj: dict) -> dict:
    return {"heads": cj["num_attention_heads"],
            "kv_heads": cj["num_key_value_heads"],
            "head_dim": cj["head_dim"],
            "rms_norm_eps": cj["rms_norm_eps"],
            "rope_theta": float(cj["rope_theta"]),
            "tie_word_embeddings": cj["tie_word_embeddings"],
            "z_loss": cj.get("z_loss", 0.0)}
