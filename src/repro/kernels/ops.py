"""Jit'd public wrappers for the Pallas kernels, with autodiff.

The causal linear attention has a Pallas backward of its own; the other
differentiable kernels (feature map, WKV-6) take the VJP of their
pure-jnp oracle (the same math). Off the TPU the kernels run with
``interpret=True``; on the TPU they compile (``_use_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.linear_attn_scan import (
    linear_attention_causal_carry_fwd, prf_mix_bwd, prf_mix_fwd)
from repro.kernels.prf_featmap import prf_featmap_fwd

Array = jax.Array


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Chunked causal linear attention (the training mix)
# ---------------------------------------------------------------------------

def _operand_dtype(x: Array):
    """Matmul operands of the causal mix: on the TPU one bf16 pass, what
    XLA gives an f32 einsum there at its default precision; elsewhere
    the input's own dtype."""
    return x.dtype if _use_interpret() else jnp.bfloat16


def train_mix_kernel(use_kernel: bool) -> bool:
    """Whether the training step's causal PRF mix takes the Pallas pair:
    always on one TPU device; off the TPU (interpreted) when
    ``use_kernel`` asks for it. A step over several devices keeps the
    XLA path, since a Mosaic kernel is not partitioned automatically."""
    if _use_interpret():
        return use_kernel
    return jax.device_count() == 1


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _lin_attn(qf: Array, kf: Array, v: Array, eps: float, block: int,
              dtypes: tuple):
    return _lin_attn_fwd(qf, kf, v, eps, block, dtypes)[0]


def _lin_attn_fwd(qf, kf, v, eps, block, dtypes):
    dt = _operand_dtype(qf)
    q, k = qf.astype(dt), kf.astype(dt)
    out, den = prf_mix_fwd(q, k, v, eps=eps, block=block,
                           interpret=_use_interpret())
    return out, (q, k, v, out, den)


def _lin_attn_bwd(eps, block, dtypes, res, g):
    q, k, v, out, den = res
    grads = prf_mix_bwd(q, k, v, g, out, den, eps=eps, block=block,
                        interpret=_use_interpret())
    return tuple(x.astype(dt) for x, dt in zip(grads, dtypes))


_lin_attn.defvjp(_lin_attn_fwd, _lin_attn_bwd)


def linear_attention_causal(qf: Array, kf: Array, v: Array, *,
                            eps: float = 1e-6, block: int = 128) -> Array:
    """Causal PRF attention through the Pallas pair of
    ``linear_attn_scan`` (forward, and a backward of its own).

    qf: (B, G, H, L, m); kf: (B, G, Hk, L, m) and v: (B, G, Hk, L, dv)
    with Hk = 1, one key head shared by the H query heads of a group, or
    Hk = H. Returns (B, G, H, L, dv) in v.dtype. The values and output
    pass to the kernels token-major, (B, L, heads x dv), the layout of
    the projections around the mix. ``block`` is the kernels' chunk of
    positions; the result does not depend on it beyond rounding.
    """
    b, g, h, l, m = qf.shape
    dv = v.shape[-1]
    if kf.shape[2] != 1:                 # each query head its own group
        qf, kf, v = (x.reshape(b, g * h, 1, l, x.shape[-1])
                     for x in (qf, kf, v))
    ng, hq = qf.shape[1:3]
    vt = jnp.moveaxis(v[:, :, 0], 1, 2).reshape(b, l, ng * dv)
    out = _lin_attn(qf, kf[:, :, 0], vt, eps, block,
                    (qf.dtype, kf.dtype, v.dtype))
    out = jnp.moveaxis(out.reshape(b, l, ng, hq, dv), 1, 3)
    return out.reshape(b, g, h, l, dv)


def linear_attention_prefill_chunk(qf: Array, kf: Array, v: Array,
                                   s: Array, z: Array, *,
                                   chunk: int = 256, eps: float = 1e-6
                                   ) -> tuple[Array, Array, Array]:
    """Advance a PRF prefix state over a prompt chunk via the Pallas scan.

    qf, kf: (..., L, m); v: (..., L, dv); s: (..., m, dv); z: (..., m) —
    leading dims are independent (batch, group, head) rows and get
    flattened. Forward-only (serving-side chunked prefill; no VJP).
    Returns (out (..., L, dv), s_new, z_new); state in f32.
    """
    lead = qf.shape[:-2]
    l, m = qf.shape[-2:]
    dv = v.shape[-1]
    out, s_new, z_new = linear_attention_causal_carry_fwd(
        qf.reshape(-1, l, m), kf.reshape(-1, l, m), v.reshape(-1, l, dv),
        jnp.broadcast_to(s, (*lead, m, dv)).reshape(-1, m, dv)
        .astype(jnp.float32),
        jnp.broadcast_to(z, (*lead, m)).reshape(-1, m).astype(jnp.float32),
        chunk=chunk, eps=eps, interpret=_use_interpret())
    return (out.reshape(*lead, l, dv), s_new.reshape(*lead, m, dv),
            z_new.reshape(*lead, m))


# ---------------------------------------------------------------------------
# One-token PRF decode step (serving)
# ---------------------------------------------------------------------------

from repro.kernels.prf_decode_step import prf_decode_step_fwd  # noqa: E402


def linear_attention_decode_step(qf: Array, kf: Array, v: Array,
                                 s: Array, z: Array, rescale: Array, *,
                                 eps: float = 1e-6, block_b: int = 8):
    """Advance the PRF serving state by one token via the Pallas kernel.

    qf, kf, z: (..., m); v: (..., dv); s: (..., m, dv); rescale: (...,)
    — leading dims are independent (batch, group, head) slots and get
    flattened. Forward-only (decode is inference; no VJP registered).
    Returns (out (..., dv), s_new, z_new), f32.
    """
    lead = qf.shape[:-1]
    m = qf.shape[-1]
    dv = v.shape[-1]
    out, s_new, z_new = prf_decode_step_fwd(
        qf.reshape(-1, m), kf.reshape(-1, m), v.reshape(-1, dv),
        s.reshape(-1, m, dv), z.reshape(-1, m),
        jnp.broadcast_to(rescale, lead).reshape(-1, 1),
        eps=eps, block_b=block_b, interpret=_use_interpret())
    return (out.reshape(*lead, dv), s_new.reshape(*lead, m, dv),
            z_new.reshape(*lead, m))


# ---------------------------------------------------------------------------
# Fused data-aligned decode megakernel (serving)
# ---------------------------------------------------------------------------

from repro.kernels.prf_fused_decode import prf_fused_decode_fwd  # noqa: E402


def fused_prf_decode(q: Array, k: Array, v: Array, a: Array,
                     m_mat: Array | None, s: Array, z: Array, c: Array,
                     *, stabilize: bool = True, eps: float = 1e-6,
                     block_b: int = 8):
    """One-token PRF decode fully fused: raw scaled q/k in, advanced
    (S, z, c) pool out, with the projection/featmap/stabilizer/update/
    readout chain in one kernel and the pool aliased in place.

    q: (B, G, Hg, d); k, v: (B, G, d|dv); a: (G, d, m) precomposed
    (W M)^T (see ``feature_maps.precompose_projection``); m_mat:
    (G, r, d) or None; s: (B, G, Hg, m, dv); z: (B, G, Hg, m);
    c: (B, G). Forward-only (decode is inference; no VJP).
    Returns (out (B, G, Hg, dv) f32, s_new, z_new, c_new (B, G)).
    """
    return prf_fused_decode_fwd(
        q, k, v.astype(jnp.float32), a, m_mat, s, z, c,
        stabilize=stabilize, eps=eps, block_b=block_b,
        interpret=_use_interpret())


# ---------------------------------------------------------------------------
# Fused data-aligned prefill megakernel (serving)
# ---------------------------------------------------------------------------

from repro.kernels.prf_fused_prefill import prf_fused_prefill_fwd  # noqa: E402


def fused_prf_prefill(q: Array, k: Array, v: Array, a: Array,
                      m_mat: Array | None, s: Array, z: Array, c: Array,
                      valid_len: Array | None = None, *,
                      stabilize: bool = True, eps: float = 1e-6,
                      chunk: int = 256, block_b: int = 1):
    """One packed prefill chunk fully fused: raw scaled q/k in, chunk
    outputs plus the advanced resumable (S, z, c) out, with the
    projection/featmap/running-max stabilizer/causal scan/state advance
    chain in one kernel per layer per chunk, ragged ``valid_len`` rows
    masked in-kernel, and the state aliased in place.

    q: (B, G, Hg, L, d); k, v: (B, G, L, d|dv); a: (G, d, m)
    precomposed (W M)^T (see ``feature_maps.precompose_projection``);
    m_mat: (G, r, d) or None; s: (B, G, Hg, m, dv); z: (B, G, Hg, m);
    c: (B, G); valid_len: (B,) int32 or None. Forward-only (serving-
    side prefill; no VJP). Returns (out (B, G, Hg, L, dv) in v.dtype,
    s_new, z_new, c_new (B, G)), state in f32.
    """
    return prf_fused_prefill_fwd(
        q, k, v, a, m_mat, s, z, c, valid_len,
        stabilize=stabilize, eps=eps, chunk=chunk, block_b=block_b,
        interpret=_use_interpret())


# ---------------------------------------------------------------------------
# Fused PRF feature map
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _featmap(x, m_mat, w, c, block_n):
    shape = x.shape
    out = prf_featmap_fwd(x.reshape(-1, shape[-1]), m_mat, w, c,
                          block_n=block_n, interpret=_use_interpret())
    return out.reshape(*shape[:-1], w.shape[0])


def _featmap_fwd(x, m_mat, w, c, block_n):
    return _featmap(x, m_mat, w, c, block_n), (x, m_mat, w, c)


def _featmap_bwd(block_n, res, g):
    x, m_mat, w, c = res
    shape = x.shape

    def f(x_, m_, w_, c_):
        return _ref.prf_featmap_ref(x_.reshape(-1, shape[-1]), m_, w_,
                                    c_).reshape(*shape[:-1], w_.shape[0])

    _, vjp = jax.vjp(f, x, m_mat, w, c)
    return vjp(g)


_featmap.defvjp(_featmap_fwd, _featmap_bwd)


def prf_featmap(x: Array, m_mat: Array | None, w: Array,
                c: Array | float = 0.0, *, block_n: int = 256) -> Array:
    """Fused phi(x) = exp(W Mx - ||Mx||^2/2 - c)/sqrt(m). Differentiable."""
    c = jnp.asarray(c, jnp.float32)
    if m_mat is None:
        # custom_vjp can't take None leaves; isotropic uses identity fold.
        return _featmap_iso(x, w, c, block_n)
    return _featmap(x, m_mat, w, c, block_n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _featmap_iso(x, w, c, block_n):
    shape = x.shape
    out = prf_featmap_fwd(x.reshape(-1, shape[-1]), None, w, c,
                          block_n=block_n, interpret=_use_interpret())
    return out.reshape(*shape[:-1], w.shape[0])


def _featmap_iso_fwd(x, w, c, block_n):
    return _featmap_iso(x, w, c, block_n), (x, w, c)


def _featmap_iso_bwd(block_n, res, g):
    x, w, c = res
    shape = x.shape

    def f(x_, w_, c_):
        return _ref.prf_featmap_ref(x_.reshape(-1, shape[-1]), None, w_,
                                    c_).reshape(*shape[:-1], w_.shape[0])

    _, vjp = jax.vjp(f, x, w, c)
    return vjp(g)


_featmap_iso.defvjp(_featmap_iso_fwd, _featmap_iso_bwd)


# ---------------------------------------------------------------------------
# Chunked WKV-6 recurrence
# ---------------------------------------------------------------------------

from repro.kernels.wkv6_scan import wkv6_fwd as _wkv6_fwd  # noqa: E402


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _wkv6(r, k, v, w, u, chunk):
    n = r.shape[:-2]
    l, dh = r.shape[-2:]
    out = _wkv6_fwd(r.reshape(-1, l, dh), k.reshape(-1, l, dh),
                    v.reshape(-1, l, dh), w.reshape(-1, l, dh), u,
                    chunk=chunk, interpret=_use_interpret())
    return out.reshape(*n, l, dh)


def _wkv6_vjp_fwd(r, k, v, w, u, chunk):
    return _wkv6(r, k, v, w, u, chunk), (r, k, v, w, u)


def _wkv6_vjp_bwd(chunk, res, g):
    r, k, v, w, u = res
    n = r.shape[:-2]
    l, dh = r.shape[-2:]

    def f(r_, k_, v_, w_, u_):
        s0 = jnp.zeros((r_.reshape(-1, l, dh).shape[0], dh, dh),
                       jnp.float32)
        o, _ = _ref.wkv6_ref(r_.reshape(-1, l, dh), k_.reshape(-1, l, dh),
                             v_.reshape(-1, l, dh), w_.reshape(-1, l, dh),
                             u_, s0)
        return o.reshape(*n, l, dh)

    _, vjp = jax.vjp(f, r, k, v, w, u)
    return vjp(g)


_wkv6.defvjp(_wkv6_vjp_fwd, _wkv6_vjp_bwd)


def wkv6(r, k, v, w, u, *, chunk: int = 256):
    """Chunked RWKV-6 WKV via the Pallas kernel; oracle-VJP backward."""
    return _wkv6(r, k, v, w, u, chunk)
