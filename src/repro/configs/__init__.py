"""Architecture registry + per-(arch x shape) input specs for the dry-run."""
from __future__ import annotations

import importlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, darkify
from repro.models import ModelConfig

ARCHS = [
    "recurrentgemma-2b",
    "smollm-135m",
    "granite-8b",
    "qwen3-32b",
    "yi-34b",
    "rwkv6-7b",
    "granite-moe-3b-a800m",
    "qwen3-moe-235b-a22b",
    "internvl2-76b",
    "hubert-xlarge",
    "darkformer-2b",           # the paper's own model (not an assigned cell)
]

ASSIGNED = ARCHS[:10]


def _module(name: str):
    return importlib.import_module(
        "repro.configs." + name.replace("-", "_"))


def get_config(name: str, reduced: bool = False, **overrides) -> ModelConfig:
    """The architecture's config with ``overrides`` replaced. An override
    of a nested config field takes a dict of that config's fields (as a
    configuration file gives it, lists for tuples): ``moe={"held": [0,
    20]}``."""
    mod = _module(name)
    cfg = mod.reduced() if reduced else mod.config()
    return _replace(cfg, overrides)


def _replace(cfg, overrides: dict):
    import dataclasses
    new = {}
    for key, val in overrides.items():
        cur = getattr(cfg, key)
        if isinstance(val, dict) and dataclasses.is_dataclass(cur):
            val = _replace(cur, val)
        elif isinstance(val, list):
            val = tuple(val)
        new[key] = val
    return dataclasses.replace(cfg, **new) if new else cfg


def cell_supported(cfg: ModelConfig, shape_name: str) -> tuple[bool, str]:
    """Whether an (arch x shape) cell applies, and why not if skipped."""
    kind = SHAPES[shape_name]["kind"]
    if not cfg.causal and kind == "decode":
        return False, "encoder-only arch: no decode step"
    if shape_name == "long_500k" and cfg.attn.kind == "exact" and \
            any(k in ("attn", "local") for k in cfg.block_pattern):
        return False, ("500k decode with exact full attention skipped; "
                       "run with a PRF kernel (the paper's point)")
    return True, ""


def input_specs(cfg: ModelConfig, shape_name: str,
                per_host_batch: Optional[int] = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell.

    Weak-type-correct, shardable, no device allocation (the dry-run
    contract). For 'decode' kinds this is the {token} input; the serving
    state is built separately via serve_state_specs_for.
    """
    sh = SHAPES[shape_name]
    b = per_host_batch or sh["global_batch"]
    l = sh["seq_len"]
    kind = sh["kind"]
    i32 = jnp.int32
    f = jnp.dtype(cfg.dtype)
    if kind == "decode":
        return {"token": jax.ShapeDtypeStruct((b,), i32)}
    if cfg.modality == "audio":
        d = {"frames": jax.ShapeDtypeStruct((b, l, cfg.d_model), f),
             "mask": jax.ShapeDtypeStruct((b, l), jnp.bool_)}
        if kind == "train":
            d["labels"] = jax.ShapeDtypeStruct((b, l), i32)
        return d
    if cfg.modality == "vlm":
        lt = l - cfg.num_patches
        d = {"tokens": jax.ShapeDtypeStruct((b, lt), i32),
             "patch_embeds": jax.ShapeDtypeStruct(
                 (b, cfg.num_patches, cfg.d_model), f)}
        if kind == "train":
            d["labels"] = jax.ShapeDtypeStruct((b, lt), i32)
        return d
    d = {"tokens": jax.ShapeDtypeStruct((b, l), i32)}
    if kind == "train":
        d["labels"] = jax.ShapeDtypeStruct((b, l), i32)
    return d
