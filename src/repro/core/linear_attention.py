"""Linear (random-feature) attention and exact-softmax references.

Layout convention everywhere: (B, H, L, D) — batch, heads, length, dim.
GQA is handled by the model layer (K/V carry n_kv heads; queries are
reshaped to (B, n_kv, group, L, D) before calling in here with H = n_kv and
the group folded into L-independent batch dims, or by repeating KV).

Three compute paths for the PRF numerator/denominator:

  * noncausal        — (Q' (K'^T V)) two-matmul form, O(L m d)
  * causal (chunked) — blockwise prefix state, O(L m d); pure-jnp version
                       here is the oracle for the Pallas kernel in
                       repro/kernels/linear_attn_scan.py
  * decode           — O(1) per-token state update (the serving path)

Exact softmax attention (causal / bidirectional / sliding-window) lives here
too, as the baseline the paper compares against.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# Exact attention baselines
# ---------------------------------------------------------------------------

def exact_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: Optional[int] = None,
                    logit_dtype=jnp.float32) -> Array:
    """Softmax attention. q,k already scaled by d^{-1/4} each.

    window: sliding-window size (Mistral/Griffin-style local attention),
    counted inclusive of the current token.
    """
    l_q, l_k = q.shape[-2], k.shape[-2]
    logits = jnp.einsum("...qd,...kd->...qk", q, k).astype(logit_dtype)
    idx_q = jnp.arange(l_q)[:, None] + (l_k - l_q)
    idx_k = jnp.arange(l_k)[None, :]
    mask = jnp.ones((l_q, l_k), dtype=bool)
    if causal:
        mask &= idx_k <= idx_q
    if window is not None:
        mask &= idx_k > idx_q - window
    logits = jnp.where(mask, logits, jnp.finfo(logit_dtype).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", probs, v).astype(v.dtype)


def constant_attention(v: Array, *, causal: bool = True) -> Array:
    """Uniform-weights baseline: causal running mean of V (paper §6)."""
    if causal:
        csum = jnp.cumsum(v.astype(jnp.float32), axis=-2)
        denom = jnp.arange(1, v.shape[-2] + 1, dtype=jnp.float32)
        return (csum / denom[:, None]).astype(v.dtype)
    return jnp.broadcast_to(jnp.mean(v, axis=-2, keepdims=True), v.shape)


def random_attention(key: Array, v: Array, *, causal: bool = True) -> Array:
    """Fixed random attention weights baseline (paper §6)."""
    l = v.shape[-2]
    logits = jax.random.normal(key, (l, l), dtype=jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((l, l), dtype=bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("qk,...kd->...qd", probs, v).astype(v.dtype)


# ---------------------------------------------------------------------------
# Linear attention — noncausal (two matmuls)
# ---------------------------------------------------------------------------

def linear_attention_noncausal(qf: Array, kf: Array, v: Array,
                               eps: float = 1e-6) -> Array:
    """(Q' (K'^T V)) / (Q' sum_j K'_j). qf,kf: (..., L, m), v: (..., L, d)."""
    kv = jnp.einsum("...lm,...ld->...md", kf.astype(jnp.float32),
                    v.astype(jnp.float32))
    num = jnp.einsum("...lm,...md->...ld", qf.astype(jnp.float32), kv)
    ksum = jnp.sum(kf.astype(jnp.float32), axis=-2)
    den = jnp.einsum("...lm,...m->...l", qf.astype(jnp.float32), ksum)
    return (num / (den[..., None] + eps)).astype(v.dtype)


# ---------------------------------------------------------------------------
# Linear attention — causal
# ---------------------------------------------------------------------------

def linear_attention_causal_naive(qf: Array, kf: Array, v: Array,
                                  eps: float = 1e-6) -> Array:
    """O(L^2) masked reference — ground truth for tests only."""
    scores = jnp.einsum("...qm,...km->...qk", qf.astype(jnp.float32),
                        kf.astype(jnp.float32))
    l = qf.shape[-2]
    mask = jnp.tril(jnp.ones((l, l), dtype=bool))
    scores = jnp.where(mask, scores, 0.0)
    num = jnp.einsum("...qk,...kd->...qd", scores, v.astype(jnp.float32))
    den = jnp.sum(scores, axis=-1, keepdims=True)
    return (num / (den + eps)).astype(v.dtype)


def linear_attention_causal_carry(qf: Array, kf: Array, v: Array,
                                  s0: Optional[Array] = None,
                                  z0: Optional[Array] = None, *,
                                  chunk: int = 256, eps: float = 1e-6
                                  ) -> tuple[Array, Array, Array]:
    """Chunked prefix-state causal linear attention from a carried state.

    The pure-jnp oracle mirroring the Pallas kernel's blocking:
      per chunk c:   out_c = Q'_c S_in + tril(Q'_c K'_c^T) V_c
                     den_c = Q'_c z_in + tril(Q'_c K'_c^T) 1
                     S_out = S_in + K'_c^T V_c ;  z_out = z_in + sum K'_c
    ``s0`` (..., m, dv) / ``z0`` (..., m) seed the scan (zeros when None,
    i.e. a fresh sequence); every position attends to the carried prefix
    plus its own causal chunk — which is what makes prefill *resumable*:
    the state after k tokens is a valid entry point for the next chunk.
    Returns (out, s_final, z_final); out in v.dtype, state in f32.
    """
    *batch, l, m = qf.shape
    dv = v.shape[-1]
    if l % chunk:
        pad = chunk - l % chunk
        qf = jnp.pad(qf, [(0, 0)] * len(batch) + [(0, pad), (0, 0)])
        kf = jnp.pad(kf, [(0, 0)] * len(batch) + [(0, pad), (0, 0)])
        v = jnp.pad(v, [(0, 0)] * len(batch) + [(0, pad), (0, 0)])
    lp = qf.shape[-2]
    nc = lp // chunk
    qc = qf.reshape(*batch, nc, chunk, m).astype(jnp.float32)
    kc = kf.reshape(*batch, nc, chunk, m).astype(jnp.float32)
    vc = v.reshape(*batch, nc, chunk, dv).astype(jnp.float32)
    tri = jnp.tril(jnp.ones((chunk, chunk), dtype=jnp.float32))

    def step(carry, xs):
        s, z = carry
        qb, kb, vb = xs
        local = jnp.einsum("...qm,...km->...qk", qb, kb) * tri
        num = jnp.einsum("...qm,...md->...qd", qb, s) + jnp.einsum(
            "...qk,...kd->...qd", local, vb)
        den = jnp.einsum("...qm,...m->...q", qb, z) + jnp.sum(local, axis=-1)
        s = s + jnp.einsum("...km,...kd->...md", kb, vb)
        z = z + jnp.sum(kb, axis=-2)
        return (s, z), (num, den)

    if s0 is None:
        s0 = jnp.zeros((*batch, m, dv), jnp.float32)
    if z0 is None:
        z0 = jnp.zeros((*batch, m), jnp.float32)
    s0 = jnp.broadcast_to(s0.astype(jnp.float32), (*batch, m, dv))
    z0 = jnp.broadcast_to(z0.astype(jnp.float32), (*batch, m))
    qs = jnp.moveaxis(qc, len(batch), 0)
    ks = jnp.moveaxis(kc, len(batch), 0)
    vs = jnp.moveaxis(vc, len(batch), 0)
    (s_f, z_f), (nums, dens) = jax.lax.scan(step, (s0, z0), (qs, ks, vs))
    nums = jnp.moveaxis(nums, 0, len(batch)).reshape(*batch, lp, dv)
    dens = jnp.moveaxis(dens, 0, len(batch)).reshape(*batch, lp)
    out = nums / (dens[..., None] + eps)
    return out[..., :l, :].astype(v.dtype), s_f, z_f


def linear_attention_causal_chunked(qf: Array, kf: Array, v: Array,
                                    chunk: int = 256,
                                    eps: float = 1e-6) -> Array:
    """Fresh-sequence (zero initial state) chunked causal linear attention."""
    out, _, _ = linear_attention_causal_carry(qf, kf, v, chunk=chunk,
                                              eps=eps)
    return out


def linear_attention_causal_blockwise(qf: Array, kf: Array, v: Array,
                                      chunk: int = 256,
                                      eps: float = 1e-6) -> Array:
    """:func:`linear_attention_causal_chunked` without its loop, for the
    training forward.

    Every chunk reads the exclusive prefix sum of the earlier chunks'
    K'^T V and sum K', so all chunks run at once. The TPU compiler
    refuses the training step of a sharded model that rematerializes the
    chunk scan inside the layer scan. Serving keeps the scan, so that a
    whole-prompt prefill rounds exactly as a resumed one.
    """
    f32 = jnp.float32
    *batch, l, m = qf.shape
    dv = v.shape[-1]
    pad = -l % chunk
    if pad:
        widths = [(0, 0)] * len(batch) + [(0, pad), (0, 0)]
        qf, kf, v = (jnp.pad(t, widths) for t in (qf, kf, v))
    nc = (l + pad) // chunk
    ax = len(batch)                                   # the chunk axis
    qc = qf.reshape(*batch, nc, chunk, m).astype(f32)
    kc = kf.reshape(*batch, nc, chunk, m).astype(f32)
    vc = v.reshape(*batch, nc, chunk, dv).astype(f32)

    def before(x):                       # sum over the earlier chunks
        run = jax.lax.slice_in_dim(jnp.cumsum(x, axis=ax), 0, nc - 1,
                                   axis=ax)
        return jnp.concatenate(
            [jnp.zeros_like(jax.lax.slice_in_dim(x, 0, 1, axis=ax)), run],
            axis=ax)

    s = before(jnp.einsum("...ckm,...ckd->...cmd", kc, vc))
    z = before(jnp.sum(kc, axis=-2))
    tri = jnp.tril(jnp.ones((chunk, chunk), dtype=f32))
    local = jnp.einsum("...cqm,...ckm->...cqk", qc, kc) * tri
    num = jnp.einsum("...cqm,...cmd->...cqd", qc, s) + jnp.einsum(
        "...cqk,...ckd->...cqd", local, vc)
    den = jnp.einsum("...cqm,...cm->...cq", qc, z) + jnp.sum(local, axis=-1)
    # the padded rows are cut before the division: their den is 0, and
    # the division's backward would square eps there
    num = num.reshape(*batch, nc * chunk, dv)[..., :l, :]
    den = den.reshape(*batch, nc * chunk)[..., :l]
    return (num / (den[..., None] + eps)).astype(v.dtype)


class LinearState(NamedTuple):
    """O(1) decode state for linear attention: S (m x dv) and z (m)."""
    s: Array   # (..., m, dv) float32
    z: Array   # (..., m)     float32

    @classmethod
    def zeros(cls, batch_shape: tuple, m: int, dv: int) -> "LinearState":
        return cls(jnp.zeros((*batch_shape, m, dv), jnp.float32),
                   jnp.zeros((*batch_shape, m), jnp.float32))


def linear_attention_prefill(qf: Array, kf: Array, v: Array,
                             chunk: int = 256,
                             eps: float = 1e-6) -> tuple[Array, LinearState]:
    """Full-sequence causal pass that also returns the final decode state."""
    out = linear_attention_causal_chunked(qf, kf, v, chunk=chunk, eps=eps)
    s = jnp.einsum("...lm,...ld->...md", kf.astype(jnp.float32),
                   v.astype(jnp.float32))
    z = jnp.sum(kf.astype(jnp.float32), axis=-2)
    return out, LinearState(s, z)


def linear_attention_decode(qf: Array, kf: Array, v: Array,
                            state: LinearState,
                            eps: float = 1e-6) -> tuple[Array, LinearState]:
    """One-token decode. qf,kf: (..., m); v: (..., dv)."""
    s = state.s + kf[..., :, None].astype(jnp.float32) * v[
        ..., None, :].astype(jnp.float32)
    z = state.z + kf.astype(jnp.float32)
    num = jnp.einsum("...m,...md->...d", qf.astype(jnp.float32), s)
    den = jnp.einsum("...m,...m->...", qf.astype(jnp.float32), z)
    out = num / (den[..., None] + eps)
    return out.astype(v.dtype), LinearState(s, z)


def sequence_parallel_state_combine(partial_states: LinearState,
                                    axis_name: str) -> LinearState:
    """SP prefill: combine per-shard prefix states with one all-reduce.

    The chunked state update is associative, so sequence-parallel prefill
    reduces to psum of partial (S, z). Used under shard_map when the
    sequence axis is sharded (beyond-paper optimization; see DESIGN §6).
    """
    return LinearState(jax.lax.psum(partial_states.s, axis_name),
                       jax.lax.psum(partial_states.z, axis_name))
