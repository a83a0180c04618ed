"""Model substrate: norms, RoPE, MLPs, MoE — pure-JAX (no flax).

Every layer is an (init, apply) pair over explicit param pytrees. Weight
layouts are chosen so the sharding rules in repro/parallel/sharding.py can
match on dict key names (see LOGICAL_AXES there).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.core import scopes

Array = jax.Array


def trunc_normal(key, shape, scale, dtype=jnp.float32):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = (scale / fan_in) ** 0.5
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                             jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.zeros((d,), dtype)}      # gemma-style (1 + scale)


def rmsnorm(params: dict, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def layernorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: dict, x: Array, eps: float = 1e-6) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


def apply_norm(kind: str, params: dict, x: Array) -> Array:
    return rmsnorm(params, x) if kind == "rmsnorm" else layernorm(params, x)


def norm_init(kind: str, d: int, dtype=jnp.float32) -> dict:
    return rmsnorm_init(d, dtype) if kind == "rmsnorm" else layernorm_init(
        d, dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> Array:
    return theta ** (-jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head)


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: (..., L, d_head); positions: (L,) or broadcastable int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (L, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, kind: str,
             dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_out": trunc_normal(k3, (d_ff, d_model), 1.0, dtype)}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = trunc_normal(k1, (d_model, d_ff), 1.0, dtype)
        p["w_up"] = trunc_normal(k2, (d_model, d_ff), 1.0, dtype)
    else:
        p["w_up"] = trunc_normal(k2, (d_model, d_ff), 1.0, dtype)
    return p


def mlp_apply(params: dict, x: Array, kind: str) -> Array:
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    elif kind == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"])
    return h @ params["w_out"]


# ---------------------------------------------------------------------------
# Mixture of Experts (top-k, dropless: sorted dispatch, grouped matmuls)
# ---------------------------------------------------------------------------

# what the "dots" remat policy saves of an expert layer by name (it
# matches dot_general, and a grouped matmul is not one): the routing's
# permutation, the dispatched rows, the grouped matmuls' outputs and
# the rows gathered back, so the backward pass neither sorts, gathers
# nor multiplies again
MOE_SAVED = "moe_saved"


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff: int                    # per-expert hidden
    aux_loss_weight: float = 0.01
    # the contiguous experts [start, stop) this device holds; None holds
    # all of them. The router always scores all ``num_experts``.
    held: Optional[tuple] = None

    @property
    def held_range(self) -> tuple[int, int]:
        return tuple(self.held) if self.held else (0, self.num_experts)

    @property
    def n_held(self) -> int:
        lo, hi = self.held_range
        return hi - lo


def moe_init(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    e, f = cfg.n_held, cfg.d_ff
    return {
        "router": trunc_normal(k1, (d_model, cfg.num_experts), 1.0,
                               jnp.float32),
        "w_gate": trunc_normal(k2, (e, d_model, f), 1.0, dtype),
        "w_up": trunc_normal(k3, (e, d_model, f), 1.0, dtype),
        "w_out": trunc_normal(k4, (e, f, d_model), 1.0, dtype),
    }


@jax.custom_vjp
def _permute_rows(x, order, inv):
    """``x[order]`` for a permutation ``order`` with inverse ``inv``;
    the backward pass is the gather ``ct[inv]``, not a scatter-add."""
    return x[order]


def _permute_rows_fwd(x, order, inv):
    return x[order], (order, inv)


def _permute_rows_bwd(res, ct):
    order, inv = res
    return ct[inv], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def moe_apply(params: dict, x: Array, cfg: MoEConfig
              ) -> tuple[Array, Array, dict]:
    """x: (B, L, d) -> (out, aux_loss, counters). Dropless top-k routing
    over the experts this device holds.

    The router scores all experts in float32 at the highest precision;
    each token takes its top k, gated by a softmax over their k logits.
    The (token, choice) pairs are sorted by expert, those routed to held
    experts first, and run through grouped matmuls (``lax.ragged_dot``)
    over the held experts' group sizes, so every such pair is computed,
    whatever the skew. Pairs routed to experts held elsewhere add
    nothing here: their rows, which no group covers, are masked on the
    way in and out (the grouped matmuls leave such rows undefined).
    ``counters``: ``load`` (held,) int32, the pairs each held expert
    took; ``away`` () int32, the pairs routed to experts not held.
    """
    b, l, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    lo, hi = cfg.held_range
    n_held, t = hi - lo, b * l
    xt = x.reshape(t, d)
    with jax.named_scope(scopes.MOE_ROUTER):
        logits = jnp.dot(xt.astype(jnp.float32), params["router"],
                         precision=jax.lax.Precision.HIGHEST)   # (T, E)
        top, idx = jax.lax.top_k(logits, k)                     # (T, K)
        gates = jax.nn.softmax(top, axis=-1)
        # load-balancing loss (Switch): E * sum_e f_e * p_e
        me = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e), axis=0)
        aux = cfg.aux_loss_weight * e * jnp.sum(me * ce)
    with jax.named_scope(scopes.MOE_DISPATCH):
        local = idx.reshape(t * k) - lo
        held = (local >= 0) & (local < n_held)
        key = jnp.where(held, local, n_held)       # away pairs sort last
        order = jnp.argsort(key, stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))
        load = jnp.sum(jax.nn.one_hot(key, n_held, dtype=jnp.int32), 0)
        order, inv, load = (checkpoint_name(a, MOE_SAVED)
                            for a in (order, inv, load))
        held_rows = (jnp.arange(t * k) < jnp.sum(load))[:, None]
        xs = checkpoint_name(jnp.where(held_rows, _permute_rows(
            jnp.repeat(xt, k, axis=0), order, inv), 0), MOE_SAVED)
    with jax.named_scope(scopes.MOE_EXPERTS):
        hg = checkpoint_name(jax.lax.ragged_dot(xs, params["w_gate"], load),
                             MOE_SAVED)
        hu = checkpoint_name(jax.lax.ragged_dot(xs, params["w_up"], load),
                             MOE_SAVED)
    h = jax.nn.silu(hg) * hu
    with jax.named_scope(scopes.MOE_EXPERTS):
        ys = checkpoint_name(jax.lax.ragged_dot(h, params["w_out"], load),
                             MOE_SAVED)
    with jax.named_scope(scopes.MOE_COMBINE):
        # back to each token's k choices, then the gated sum
        ys = jnp.where(held_rows, ys, 0)
        y = checkpoint_name(_permute_rows(ys, inv, order), MOE_SAVED
                            ).reshape(t, k, d)
        out = jnp.einsum("tkd,tk->td", y, gates.astype(y.dtype))
    counters = {"load": load, "away": t * k - jnp.sum(load)}
    return out.reshape(b, l, d), aux, counters
