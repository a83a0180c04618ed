"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are tested against (interpret=True
on CPU; compiled on TPU) and the fallback used in autodiff backward passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def linear_attention_causal_ref(qf: Array, kf: Array, v: Array,
                                eps: float = 1e-6) -> Array:
    """Causal linear attention, O(L^2) masked form. qf,kf: (N, L, m);
    v: (N, L, dv). N = flattened batch*heads."""
    scores = jnp.einsum("nqm,nkm->nqk", qf.astype(jnp.float32),
                        kf.astype(jnp.float32))
    l = qf.shape[1]
    mask = jnp.tril(jnp.ones((l, l), dtype=bool))
    scores = jnp.where(mask[None], scores, 0.0)
    num = jnp.einsum("nqk,nkd->nqd", scores, v.astype(jnp.float32))
    den = jnp.sum(scores, axis=-1, keepdims=True)
    return (num / (den + eps)).astype(v.dtype)


def prf_featmap_ref(x: Array, m_mat: Array | None, w: Array,
                    c: Array) -> Array:
    """DARKFormer/Performer feature map. x: (N, d); m_mat: (r, d) or None
    (isotropic); w: (m, r); c: scalar stabilizer. Returns (N, m) f32."""
    x = x.astype(jnp.float32)
    if m_mat is not None:
        x = x @ m_mat.astype(jnp.float32).T
    logits = x @ w.astype(jnp.float32).T
    sq = 0.5 * jnp.sum(jnp.square(x), axis=-1, keepdims=True)
    m = w.shape[0]
    return jnp.exp(logits - sq - c) * (m ** -0.5)


def linear_attention_carry_ref(qf: Array, kf: Array, v: Array,
                               s0: Array, z0: Array, eps: float = 1e-6):
    """Causal linear attention resumed from a prefix state — O(L^2) masked
    oracle for the carry kernel. qf, kf: (N, L, m); v: (N, L, dv);
    s0: (N, m, dv); z0: (N, m). Returns (out, s_new, z_new)."""
    f32 = jnp.float32
    qf, kf, v, s0, z0 = (t.astype(f32) for t in (qf, kf, v, s0, z0))
    scores = jnp.einsum("nqm,nkm->nqk", qf, kf)
    l = qf.shape[1]
    mask = jnp.tril(jnp.ones((l, l), dtype=bool))
    scores = jnp.where(mask[None], scores, 0.0)
    num = jnp.einsum("nqm,nmd->nqd", qf, s0) + jnp.einsum(
        "nqk,nkd->nqd", scores, v)
    den = (jnp.einsum("nqm,nm->nq", qf, z0)
           + jnp.sum(scores, axis=-1))[..., None]
    s_new = s0 + jnp.einsum("nlm,nld->nmd", kf, v)
    z_new = z0 + jnp.sum(kf, axis=1)
    return num / (den + eps), s_new, z_new


def prf_decode_step_ref(qf: Array, kf: Array, v: Array, s: Array,
                        z: Array, rescale: Array, eps: float = 1e-6):
    """One-token PRF decode oracle. qf, kf, z: (N, m); v: (N, dv);
    s: (N, m, dv); rescale: (N, 1). Returns (out, s_new, z_new), f32."""
    f32 = jnp.float32
    qf, kf, v, s, z, rescale = (t.astype(f32)
                                for t in (qf, kf, v, s, z, rescale))
    s_new = s * rescale[:, :, None] + kf[:, :, None] * v[:, None, :]
    z_new = z * rescale + kf
    num = jnp.einsum("nm,nmd->nd", qf, s_new)
    den = jnp.einsum("nm,nm->n", qf, z_new)[:, None]
    return num / (den + eps), s_new, z_new


def rglru_ref(x: Array, a: Array, gate: Array, h0: Array) -> tuple[Array,
                                                                   Array]:
    """RG-LRU diagonal recurrence oracle (Griffin, arXiv:2402.19427).

    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (g_t * x_t)
    x, a, gate: (N, L, d) with a in (0, 1); h0: (N, d).
    Returns (h_all (N, L, d), h_last (N, d)).
    """
    x = x.astype(jnp.float32)
    a = a.astype(jnp.float32)
    inp = jnp.sqrt(jnp.clip(1.0 - a * a, 0.0)) * (
        gate.astype(jnp.float32) * x)

    def step(h, xs):
        a_t, i_t = xs
        h = a_t * h + i_t
        return h, h

    hl, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                          (jnp.moveaxis(a, 1, 0), jnp.moveaxis(inp, 1, 0)))
    return jnp.moveaxis(hs, 0, 1), hl


def wkv6_ref(r: Array, k: Array, v: Array, w: Array, u: Array,
             s0: Array) -> tuple[Array, Array]:
    """RWKV-6 WKV recurrence oracle (arXiv:2404.05892).

    Per head: S_t = diag(w_t) S_{t-1} + k_t v_t^T
              o_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    r,k,v,w: (N, L, dh); u: (dh,); s0: (N, dh, dh). w_t in (0,1) decay.
    Returns (o (N, L, dh), s_last).
    """
    def step(s, xs):
        r_t, k_t, v_t, w_t = xs
        kv = k_t[:, :, None] * v_t[:, None, :]
        o = jnp.einsum("nd,nde->ne", r_t, s + u[None, :, None] * kv)
        s = w_t[:, :, None] * s + kv
        return s, o

    args = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                 for t in (r, k, v, w))
    s_last, outs = jax.lax.scan(step, s0.astype(jnp.float32), args)
    return jnp.moveaxis(outs, 0, 1), s_last


def prf_fused_prefill_ref(q: Array, k: Array, v: Array, a: Array,
                          m_mat: Array | None, s: Array, z: Array,
                          c: Array, valid_len: Array | None = None, *,
                          stabilize: bool = True, eps: float = 1e-6):
    """Fused data-aligned PRF prefill-chunk oracle — projection, exp
    feature map with the running-max k-stabilizer (ONE max over the
    whole chunk, the jnp ``_resume_qk_features`` trajectory), ragged
    ``valid_len`` masking, causal carried-state attention and the
    resumable (S, z, c) advance, all from RAW scaled q/k.

    q: (B, G, Hg, L, d); k, v: (B, G, L, d|dv); a: (G, d, m)
    precomposed (W M)^T; m_mat: (G, r, d) or None (isotropic norm);
    s: (B, G, Hg, m, dv); z: (B, G, Hg, m); c: (B, G); valid_len:
    (B,) int32 or None (all rows full). Returns (out (B, G, Hg, L, dv)
    f32, s_new, z_new, c_new), with outputs 0 at masked positions.
    """
    f32 = jnp.float32
    q, k, v, a, s, z, c = (t.astype(f32)
                           for t in (q, k, v, a, s, z, c))
    b, g, hg, l, _ = q.shape
    m = a.shape[-1]
    dv = v.shape[-1]
    inv_sqrt_m = m ** -0.5
    neg = jnp.finfo(f32).min

    def raw(x, eq):
        logits = jnp.einsum(eq + ",gdm->" + eq.replace("d", "m"), x, a)
        xt = x if m_mat is None else jnp.einsum(
            eq + ",grd->" + eq.replace("d", "r"), x, m_mat.astype(f32))
        return logits - 0.5 * jnp.sum(xt * xt, -1, keepdims=True)

    qraw = raw(q, "bghld")                               # (B,G,Hg,L,m)
    kraw = raw(k, "bgld")                                # (B,G,L,m)
    if valid_len is None:
        valid = jnp.ones((b, l), bool)
    else:
        valid = jnp.arange(l)[None] < valid_len[:, None]
    kraw_m = jnp.where(valid[:, None, :, None], kraw, neg)
    if stabilize:
        c_new = jnp.maximum(c, jnp.max(kraw_m, axis=(-2, -1)))
        rho = jnp.exp(c - c_new)
        kf = jnp.exp(kraw - c_new[..., None, None]) * inv_sqrt_m
        qraw_m = jnp.where(valid[:, None, None, :, None], qraw, neg)
        qf = jnp.exp(qraw - jnp.max(qraw_m, axis=(-2, -1),
                                    keepdims=True)) * inv_sqrt_m
    else:
        c_new = jnp.zeros_like(c)
        rho = jnp.exp(c)
        kf = jnp.exp(kraw) * inv_sqrt_m
        qf = jnp.exp(qraw) * inv_sqrt_m
    kf = jnp.where(valid[:, None, :, None], kf, 0.0)
    qf = jnp.where(valid[:, None, None, :, None], qf, 0.0)

    kfb = jnp.broadcast_to(kf[:, :, None], (b, g, hg, l, m))
    vb = jnp.broadcast_to(v[:, :, None], (b, g, hg, l, dv))
    s0 = s * rho[:, :, None, None, None]
    z0 = z * rho[:, :, None, None]
    out, s_new, z_new = linear_attention_carry_ref(
        qf.reshape(-1, l, m), kfb.reshape(-1, l, m),
        vb.reshape(-1, l, dv), s0.reshape(-1, m, dv),
        z0.reshape(-1, m), eps=eps)
    return (out.reshape(b, g, hg, l, dv),
            s_new.reshape(b, g, hg, m, dv),
            z_new.reshape(b, g, hg, m), c_new)


def prf_fused_decode_ref(q: Array, k: Array, v: Array, a: Array,
                         m_mat: Array | None, s: Array, z: Array,
                         c: Array, *, stabilize: bool = True,
                         eps: float = 1e-6):
    """Fused data-aligned PRF decode oracle — projection, exp feature
    map with the online running-max k-stabilizer, rank-1 (S, z) update
    and readout, all from RAW scaled q/k.

    q: (B, G, Hg, d); k, v: (B, G, d|dv); a: (G, d, m) precomposed
    (W M)^T; m_mat: (G, r, d) or None (isotropic norm); s: (B, G, Hg,
    m, dv); z: (B, G, Hg, m); c: (B, G). Returns (out, s_new, z_new,
    c_new), f32.
    """
    f32 = jnp.float32
    q, k, v, a, s, z, c = (t.astype(f32)
                           for t in (q, k, v, a, s, z, c))
    m = a.shape[-1]
    inv_sqrt_m = m ** -0.5

    def raw(x, eq):
        logits = jnp.einsum(eq + ",gdm->" + eq.replace("d", "m"), x, a)
        xt = x if m_mat is None else jnp.einsum(
            eq + ",grd->" + eq.replace("d", "r"), x,
            m_mat.astype(f32))
        return logits - 0.5 * jnp.sum(xt * xt, -1, keepdims=True)

    qraw = raw(q, "bghd")                                # (B, G, Hg, m)
    kraw = raw(k, "bgd")                                 # (B, G, m)
    if stabilize:
        qf = jnp.exp(qraw - jnp.max(qraw, -1, keepdims=True)) * inv_sqrt_m
        c_new = jnp.maximum(c, jnp.max(kraw, -1))
        rho = jnp.exp(c - c_new)
        kf = jnp.exp(kraw - c_new[..., None]) * inv_sqrt_m
    else:
        qf = jnp.exp(qraw) * inv_sqrt_m
        c_new = jnp.zeros_like(c)
        rho = jnp.exp(c)
        kf = jnp.exp(kraw) * inv_sqrt_m
    r4 = rho[:, :, None, None, None]                     # (B,G,1,1,1)
    s_new = s * r4 + kf[:, :, None, :, None] * v[:, :, None, None, :]
    z_new = z * rho[:, :, None, None] + kf[:, :, None, :]
    num = jnp.einsum("bghm,bghmd->bghd", qf, s_new)
    den = jnp.einsum("bghm,bghm->bgh", qf, z_new)[..., None]
    return num / (den + eps), s_new, z_new, c_new
