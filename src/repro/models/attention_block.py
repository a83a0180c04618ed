"""Attention mixer block: projections + RoPE + (RF|exact) attention + serve.

This is where the paper's technique plugs into the transformer: the block
owns per-KV-group feature params ({"w", "m_mat"}) alongside q/k/v/o, and
dispatches on FeatureConfig.kind. GQA layout throughout:
  q -> (B, G, Hg, L, dh);  k, v -> (B, G, 1, L, dh).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import attention as rfa
from repro.core import feature_maps as fm
from repro.core import scopes
from repro.models import layers as ll

Array = jax.Array


def attn_init(key, d_model: int, n_heads: int, n_kv: int, d_head: int,
              cfg: fm.FeatureConfig, qk_norm: bool = False,
              dtype=jnp.float32) -> dict:
    kq, kk, kv, ko, kf = jax.random.split(key, 5)
    p = {
        "wq": ll.trunc_normal(kq, (d_model, n_heads * d_head), 1.0, dtype),
        "wk": ll.trunc_normal(kk, (d_model, n_kv * d_head), 1.0, dtype),
        "wv": ll.trunc_normal(kv, (d_model, n_kv * d_head), 1.0, dtype),
        "wo": ll.trunc_normal(ko, (n_heads * d_head, d_model), 1.0, dtype),
    }
    if cfg.kind in ("performer", "darkformer", "lfk"):
        p["feat"] = fm.init_feature_params(kf, cfg, d_head, n_groups=n_kv,
                                           dtype=jnp.float32)
    if qk_norm:
        p["q_norm"] = ll.rmsnorm_init(d_head, dtype)
        p["k_norm"] = ll.rmsnorm_init(d_head, dtype)
    return p


def _project(params, x, n_heads, n_kv, d_head, qk_norm, positions,
             rope_theta):
    with jax.named_scope(scopes.ATTN_IN):
        b, l, _ = x.shape
        hg = n_heads // n_kv
        q = (x @ params["wq"]).reshape(b, l, n_kv, hg, d_head)
        k = (x @ params["wk"]).reshape(b, l, n_kv, 1, d_head)
        v = (x @ params["wv"]).reshape(b, l, n_kv, 1, d_head)
        q = jnp.moveaxis(q, 1, 3)          # (B, G, Hg, L, dh)
        k = jnp.moveaxis(k, 1, 3)
        v = jnp.moveaxis(v, 1, 3)
        if qk_norm:
            q = ll.rmsnorm(params["q_norm"], q)
            k = ll.rmsnorm(params["k_norm"], k)
        if rope_theta > 0:
            q = ll.apply_rope(q, positions, rope_theta)
            k = ll.apply_rope(k, positions, rope_theta)
    return q, k, v


def _merge_heads(out, params):
    # out: (B, G, Hg, L, dh) -> (B, L, H*dh) @ wo
    with jax.named_scope(scopes.ATTN_OUT):
        b, g, hg, l, dh = out.shape
        out = jnp.moveaxis(out, 3, 1).reshape(b, l, g * hg * dh)
        return out @ params["wo"]


def attn_apply(params: dict, x: Array, cfg: fm.FeatureConfig, *,
               n_heads: int, n_kv: int, d_head: int,
               causal: bool = True, window: Optional[int] = None,
               qk_norm: bool = False, rope_theta: float = 10000.0,
               positions: Optional[Array] = None,
               use_kernel: bool = False,
               baseline_key: Optional[Array] = None,
               attention_multiplier: Optional[float] = None) -> Array:
    l = x.shape[1]
    if positions is None:
        positions = jnp.arange(l)
    q, k, v = _project(params, x, n_heads, n_kv, d_head, qk_norm,
                       positions, rope_theta)
    out = rfa.rf_attention(q, k, v, params.get("feat"), cfg, causal=causal,
                           window=window, use_kernel=use_kernel,
                           baseline_key=baseline_key,
                           attention_multiplier=attention_multiplier)
    return _merge_heads(out, params)


def attn_prefill(params, x, cfg, *, n_heads, n_kv, d_head,
                 window=None, qk_norm=False, rope_theta=10000.0,
                 max_len=None, use_kernel=False, state=None,
                 position=None, valid_len=None, proj=None,
                 attention_multiplier=None):
    """Prefill one prompt chunk. ``state=None`` + ``position=None`` is the
    legacy whole-prompt call; with an incoming serve ``state`` and a chunk
    start ``position`` (() int32, or (B,) per-slot starts) the pass
    resumes: RoPE rotates at absolute positions and the attention state
    advances from where the previous chunk left it. ``valid_len`` ((B,)
    int32) marks ragged rows in a padded multi-admission chunk — see
    ``rfa.rf_attention_prefill``. ``proj`` is the block's precomposed
    projection (``fm.precompose_projection``) selecting the fused
    prefill megakernel under ``use_kernel``."""
    l = x.shape[1]
    if position is None:
        positions = jnp.arange(l)
    elif position.ndim == 0:
        positions = position + jnp.arange(l)
    else:                      # (B,) per-row starts -> (B, 1, 1, L)
        b = x.shape[0]
        positions = (position[:, None]
                     + jnp.arange(l)[None]).reshape(b, 1, 1, l)
    q, k, v = _project(params, x, n_heads, n_kv, d_head, qk_norm,
                       positions, rope_theta)
    out, state = rfa.rf_attention_prefill(
        q, k, v, params.get("feat"), cfg, window=window,
        max_len=max_len, use_kernel=use_kernel, state=state,
        valid_len=valid_len, proj=proj,
        attention_multiplier=attention_multiplier)
    return _merge_heads(out, params), state


def attn_decode(params, x, state, cfg, *, n_heads, n_kv, d_head,
                position, window=None, qk_norm=False, rope_theta=10000.0,
                use_kernel=False, proj=None, attention_multiplier=None):
    """x: (B, 1, d_model); position: () int32 current index, or (B,)
    int32 per-slot positions (continuous batching — each slot RoPE-rotates
    by its own sequence position). ``proj`` is the block's precomposed
    decode projection (``fm.precompose_projection``) selecting the fused
    megakernel path under ``use_kernel``."""
    if position.ndim == 0:
        positions = position[None]                       # (1,) -> all rows
    else:
        positions = position.reshape(-1, 1, 1, 1)        # (B,1,1,1)
    q, k, v = _project(params, x, n_heads, n_kv, d_head, qk_norm,
                       positions, rope_theta)
    out, state = rfa.rf_attention_decode(q, k, v, state,
                                         params.get("feat"), cfg,
                                         window=window,
                                         use_kernel=use_kernel,
                                         proj=proj,
                                         attention_multiplier=(
                                             attention_multiplier))
    return _merge_heads(out, params), state


def init_attn_serve_state(cfg: fm.FeatureConfig, b, n_heads, n_kv, d_head,
                          max_len, window=None,
                          per_slot=False) -> rfa.AttnServeState:
    """ShapeDtype-consistent initial serving state for one attention block.

    ``per_slot`` gives the exact-attention cache a (B,) length vector so
    each batch row (serving slot) tracks its own write index.
    """
    hg = n_heads // n_kv
    if cfg.kind == "exact":
        # NOTE: window mode could use a rolling buffer of size `window`;
        # we keep the full-length cache (decode writes at absolute idx).
        lmax = max_len
        return rfa.AttnServeState(
            kv_k=jnp.zeros((b, n_kv, lmax, d_head), jnp.float32),
            kv_v=jnp.zeros((b, n_kv, lmax, d_head), jnp.float32),
            length=jnp.zeros((b,) if per_slot else (), jnp.int32))
    return rfa.init_linear_serve_state(b, n_kv, hg, cfg.num_features,
                                       d_head)


def init_paged_attn_state(b: int, max_pages: int) -> rfa.AttnServeState:
    """Detached paged exact-KV serve state for one attention block: a
    per-row page table + write index, with ``kv_k``/``kv_v`` left None.
    The shared page pools live OUTSIDE the slot pool (they have no slot
    axis — see ``lm.init_kv_pages``) and are attached around each jitted
    step (``lm.attach_kv_pages``); the slot-pool ops in
    repro/serving/slots.py skip the None leaves."""
    return rfa.AttnServeState(
        length=jnp.zeros((b,), jnp.int32),
        table=jnp.zeros((b, max_pages), jnp.int32))
