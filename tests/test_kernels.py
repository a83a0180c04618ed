"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import linear_attention as la
from repro.kernels import ops, ref
from repro.kernels.linear_attn_scan import prf_mix_fwd
from repro.kernels.prf_featmap import prf_featmap_fwd


def _fwd(qf, kf, v, chunk):
    """The training forward kernel on (N, L, .) rows: one batch row of
    N groups of one query head, values and output token-major."""
    n, l, dv = v.shape
    out, _ = prf_mix_fwd(qf[None, :, None], kf[None],
                         jnp.moveaxis(v, 0, 1).reshape(1, l, n * dv),
                         block=chunk, interpret=True)
    return jnp.moveaxis(out.reshape(l, n, dv), 0, 1)


@pytest.mark.parametrize("n,l,m,dv,chunk", [
    (1, 8, 4, 4, 4),
    (4, 96, 32, 16, 32),
    (2, 128, 64, 32, 64),
    (3, 100, 16, 8, 32),          # non-divisible L -> padding path
    (2, 64, 48, 24, 64),          # chunk == L
])
def test_linear_attn_kernel_shapes(n, l, m, dv, chunk):
    key = jax.random.PRNGKey(l * 7 + m)
    kq, kk, kv = jax.random.split(key, 3)
    qf = jax.random.uniform(kq, (n, l, m))
    kf = jax.random.uniform(kk, (n, l, m))
    v = jax.random.normal(kv, (n, l, dv))
    out = _fwd(qf, kf, v, chunk)
    expect = ref.linear_attention_causal_ref(qf, kf, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_attn_kernel_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    qf = jax.random.uniform(kq, (2, 64, 16)).astype(dtype)
    kf = jax.random.uniform(kk, (2, 64, 16)).astype(dtype)
    v = jax.random.normal(kv, (2, 64, 8)).astype(dtype)
    out = _fwd(qf, kf, v, 32)
    expect = ref.linear_attention_causal_ref(qf, kf, v)
    assert out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


def _group_inputs(b, g, hg, l, m, dv, seed):
    """Positive features as the model makes them (one key head per
    group of ``hg`` query heads), values and an output cotangent."""
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(seed), 4)
    qf = jnp.exp(0.5 * jax.random.normal(kq, (b, g, hg, l, m))) * m ** -0.5
    kf = jnp.exp(0.5 * jax.random.normal(kk, (b, g, 1, l, m))) * m ** -0.5
    v = jax.random.normal(kv, (b, g, 1, l, dv))
    ct = jax.random.normal(kg, (b, g, hg, l, dv))
    return qf, kf, v, ct


def _blockwise(qf, kf, v, eps, chunk):
    return la.linear_attention_causal_blockwise(
        qf, jnp.broadcast_to(kf, qf.shape),
        jnp.broadcast_to(v, qf.shape[:-1] + v.shape[-1:]), chunk=chunk,
        eps=eps)


def test_linear_attn_gradients_match_oracle():
    """Gradients of the Pallas pair (its own backward) match autodiff of
    the blockwise XLA path."""
    qf, kf, v, _ = _group_inputs(2, 2, 3, 48, 16, 8, seed=1)

    def l_kernel(q, k, v_):
        return jnp.sum(ops.linear_attention_causal(q, k, v_, block=16)
                       ** 2)

    def l_ref(q, k, v_):
        return jnp.sum(_blockwise(q, k, v_, 1e-6, 16) ** 2)

    g1 = jax.grad(l_kernel, argnums=(0, 1, 2))(qf, kf, v)
    g2 = jax.grad(l_ref, argnums=(0, 1, 2))(qf, kf, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# Hg query heads per key head, value width, length, kernel chunk T and
# the denominator's floor: each value of each covered; L % T != 0 pads
@pytest.mark.parametrize("hg,dv,l,block,eps", [
    (1, 64, 64, 32, 1e-30),
    (3, 64, 72, 32, 1e-8),
    (4, 64, 64, 16, 1e-8),
    (1, 128, 40, 16, 1e-8),
    (3, 128, 64, 64, 1e-30),
    (4, 128, 56, 32, 1e-30),
])
def test_prf_mix_matches_blockwise(hg, dv, l, block, eps):
    """Forward and the gradients for qf, kf and v of the Pallas pair
    against ``linear_attention_causal_blockwise`` and its ``jax.vjp``."""
    qf, kf, v, g = _group_inputs(2, 2, hg, l, 32, dv, seed=hg * dv + l)
    out, vjp = jax.vjp(lambda *a: ops.linear_attention_causal(
        *a, eps=eps, block=block), qf, kf, v)
    want, vjp_ref = jax.vjp(lambda *a: _blockwise(*a, eps, 16), qf, kf, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    for name, a, b in zip(("dqf", "dkf", "dv"), vjp(g), vjp_ref(g)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("n,d,r,m,blk", [
    (16, 8, 4, 16, 8),
    (70, 16, 8, 64, 32),          # padding path
    (128, 32, 32, 128, 64),
])
def test_featmap_kernel_dark(n, d, r, m, blk):
    key = jax.random.PRNGKey(n + d)
    kx, km, kw = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n, d))
    m_mat = 0.3 * jax.random.normal(km, (r, d))
    w = jax.random.normal(kw, (m, r))
    out = prf_featmap_fwd(x, m_mat, w, jnp.float32(0.7), block_n=blk,
                          interpret=True)
    expect = ref.prf_featmap_ref(x, m_mat, w, jnp.float32(0.7))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=1e-6)


def test_featmap_kernel_iso():
    key = jax.random.PRNGKey(3)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (40, 8))
    w = jax.random.normal(kw, (32, 8))
    out = prf_featmap_fwd(x, None, w, jnp.float32(0.0), block_n=16,
                          interpret=True)
    expect = ref.prf_featmap_ref(x, None, w, jnp.float32(0.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=1e-6)


def test_featmap_gradients():
    key = jax.random.PRNGKey(4)
    kx, km, kw = jax.random.split(key, 3)
    x = jax.random.normal(kx, (20, 8))
    m_mat = 0.3 * jax.random.normal(km, (4, 8))
    w = jax.random.normal(kw, (16, 4))

    def lk(m_):
        return jnp.sum(ops.prf_featmap(x, m_, w, 0.5, block_n=8) ** 2)

    def lr(m_):
        return jnp.sum(ref.prf_featmap_ref(x, m_, w,
                                           jnp.float32(0.5)) ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(lk)(m_mat)),
                               np.asarray(jax.grad(lr)(m_mat)), atol=1e-4)


def test_kernel_jit_and_vmap_compose():
    """The Pallas pair under jit and vmap (over a batch of groups of
    three heads sharing a key head), forward and gradients, against the
    masked O(L^2) oracle per head."""
    qf, kf, v, _ = _group_inputs(4, 2, 3, 32, 8, 4, seed=0)
    qf, kf, v = (x.reshape(2, 2, *x.shape[1:]) for x in (qf, kf, v))

    def mix(q, k, v_):
        return ops.linear_attention_causal(q, k, v_, block=16)

    def oracle(q, k, v_):
        q3 = q.reshape(-1, 32, 8)
        k3 = jnp.broadcast_to(k, q.shape).reshape(-1, 32, 8)
        v3 = jnp.broadcast_to(v_, q.shape[:-1] + (4,)).reshape(-1, 32, 4)
        return ref.linear_attention_causal_ref(q3, k3, v3).reshape(
            *q.shape[:-1], 4)

    out = jax.jit(jax.vmap(mix))(qf, kf, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(oracle(qf, kf, v)), atol=2e-5)
    g1 = jax.jit(jax.grad(lambda *a: jnp.sum(jax.vmap(mix)(*a) ** 2),
                          argnums=(0, 1, 2)))(qf, kf, v)
    g2 = jax.grad(lambda *a: jnp.sum(oracle(*a) ** 2),
                  argnums=(0, 1, 2))(qf, kf, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_rglru_ref_matches_manual_loop():
    key = jax.random.PRNGKey(5)
    n, l, d = 2, 10, 4
    x = jax.random.normal(key, (n, l, d))
    a = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 1),
                                         (n, l, d)))
    g = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 2),
                                         (n, l, d)))
    h0 = jnp.zeros((n, d))
    hs, hl = ref.rglru_ref(x, a, g, h0)
    h = np.zeros((n, d), np.float32)
    for t in range(l):
        at = np.asarray(a[:, t])
        it = np.sqrt(np.clip(1 - at * at, 0, None)) * np.asarray(
            g[:, t]) * np.asarray(x[:, t])
        h = at * h + it
        np.testing.assert_allclose(np.asarray(hs[:, t]), h, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hl), h, atol=1e-5)


def test_wkv6_ref_matches_manual_loop():
    key = jax.random.PRNGKey(6)
    n, l, dh = 2, 6, 4
    r = jax.random.normal(key, (n, l, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (n, l, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (n, l, dh))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3),
                                         (n, l, dh)))
    u = 0.3 * jnp.ones((dh,))
    s0 = jnp.zeros((n, dh, dh))
    o, s_last = ref.wkv6_ref(r, k, v, w, u, s0)
    s = np.zeros((n, dh, dh), np.float32)
    for t in range(l):
        kv = np.asarray(k[:, t])[:, :, None] * np.asarray(v[:, t])[:, None]
        ot = np.einsum("nd,nde->ne", np.asarray(r[:, t]),
                       s + np.asarray(u)[None, :, None] * kv)
        np.testing.assert_allclose(np.asarray(o[:, t]), ot, atol=1e-5)
        s = np.asarray(w[:, t])[:, :, None] * s + kv
    np.testing.assert_allclose(np.asarray(s_last), s, atol=1e-5)


@pytest.mark.parametrize("n,l,dh,chunk", [
    (2, 16, 4, 8),
    (3, 50, 8, 16),          # padding path
    (1, 64, 16, 64),
])
def test_wkv6_kernel_vs_ref(n, l, dh, chunk):
    from repro.kernels.wkv6_scan import wkv6_fwd
    key = jax.random.PRNGKey(l + dh)
    r = jax.random.normal(key, (n, l, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (n, l, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (n, l, dh))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3),
                                         (n, l, dh)) + 2.0)
    u = 0.3 * jnp.ones((dh,))
    out = wkv6_fwd(r, k, v, w, u, chunk=chunk, interpret=True)
    expect, _ = ref.wkv6_ref(r, k, v, w, u, jnp.zeros((n, dh, dh)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=3e-5)


def test_wkv6_ops_gradients():
    key = jax.random.PRNGKey(9)
    n, l, dh = 2, 24, 4
    r = jax.random.normal(key, (n, l, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (n, l, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (n, l, dh))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3),
                                         (n, l, dh)) + 2.0)
    u = 0.3 * jnp.ones((dh,))

    def lk(r_):
        return jnp.sum(ops.wkv6(r_, k, v, w, u, chunk=8) ** 2)

    def lr(r_):
        o, _ = ref.wkv6_ref(r_, k, v, w, u, jnp.zeros((n, dh, dh)))
        return jnp.sum(o ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(lk)(r)),
                               np.asarray(jax.grad(lr)(r)), atol=2e-4)


# ---------------------------------------------------------------------------
# prf_decode_step: one-token serving update
# ---------------------------------------------------------------------------

from repro.kernels.prf_decode_step import prf_decode_step_fwd  # noqa: E402


@pytest.mark.parametrize("n,m,dv,block_b", [
    (1, 8, 4, 8),
    (16, 32, 16, 8),
    (13, 16, 8, 8),               # n % block_b != 0 -> padding path
    (6, 64, 32, 4),
    (3, 24, 12, 16),              # block_b > n -> clamped tile
])
def test_prf_decode_step_vs_ref(n, m, dv, block_b):
    key = jax.random.PRNGKey(n * 31 + m)
    kq, kk, kv, ks, kz, kr = jax.random.split(key, 6)
    qf = jax.random.uniform(kq, (n, m))
    kf = jax.random.uniform(kk, (n, m))
    v = jax.random.normal(kv, (n, dv))
    s = jax.random.normal(ks, (n, m, dv))
    z = jax.random.uniform(kz, (n, m)) + 0.5
    # online-stabilizer rescale in (0, 1] as produced by exp(c_old-c_new)
    rescale = jax.random.uniform(kr, (n, 1), minval=0.05, maxval=1.0)
    out, s_new, z_new = prf_decode_step_fwd(qf, kf, v, s, z, rescale,
                                            block_b=block_b,
                                            interpret=True)
    eo, es, ez = ref.prf_decode_step_ref(qf, kf, v, s, z, rescale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(eo), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_new), np.asarray(es),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(z_new), np.asarray(ez),
                               atol=2e-5)


def test_prf_decode_step_ops_wrapper_shapes():
    """ops.linear_attention_decode_step flattens (B,G,Hg) leads and
    broadcasts a (B,G,1) rescale across heads."""
    key = jax.random.PRNGKey(5)
    b, g, hg, m, dv = 2, 3, 2, 16, 8
    kq, kk, kv, ks, kz, kr = jax.random.split(key, 6)
    qf = jax.random.uniform(kq, (b, g, hg, m))
    kf = jax.random.uniform(kk, (b, g, hg, m))
    v = jax.random.normal(kv, (b, g, hg, dv))
    s = jax.random.normal(ks, (b, g, hg, m, dv))
    z = jax.random.uniform(kz, (b, g, hg, m)) + 0.5
    rescale = jax.random.uniform(kr, (b, g, 1), minval=0.1, maxval=1.0)
    out, s_new, z_new = ops.linear_attention_decode_step(
        qf, kf, v, s, z, rescale)
    assert out.shape == (b, g, hg, dv)
    assert s_new.shape == (b, g, hg, m, dv)
    assert z_new.shape == (b, g, hg, m)
    eo, es, ez = ref.prf_decode_step_ref(
        qf.reshape(-1, m), kf.reshape(-1, m), v.reshape(-1, dv),
        s.reshape(-1, m, dv), z.reshape(-1, m),
        jnp.broadcast_to(rescale, (b, g, hg)).reshape(-1, 1))
    np.testing.assert_allclose(np.asarray(out).reshape(-1, dv),
                               np.asarray(eo), atol=2e-5)


# ---------------------------------------------------------------------------
# Carried-state (chunked prefill) scan kernel
# ---------------------------------------------------------------------------

from repro.kernels.linear_attn_scan import (  # noqa: E402
    linear_attention_causal_carry_fwd)


def _carry_inputs(n, l, m, dv, seed=0):
    key = jax.random.PRNGKey(seed)
    kq, kk, kv, ks, kz = jax.random.split(key, 5)
    qf = jax.random.uniform(kq, (n, l, m))
    kf = jax.random.uniform(kk, (n, l, m))
    v = jax.random.normal(kv, (n, l, dv))
    s0 = jax.random.normal(ks, (n, m, dv))
    z0 = jax.random.uniform(kz, (n, m)) * 4.0
    return qf, kf, v, s0, z0


@pytest.mark.parametrize("n,l,m,dv,chunk", [
    (2, 32, 16, 8, 16),
    (3, 37, 16, 8, 16),           # non-divisible L -> padding path
    (1, 8, 4, 4, 8),              # chunk == L
])
def test_carry_kernel_matches_oracle(n, l, m, dv, chunk):
    qf, kf, v, s0, z0 = _carry_inputs(n, l, m, dv, seed=l)
    out, s, z = linear_attention_causal_carry_fwd(
        qf, kf, v, s0, z0, chunk=chunk, interpret=True)
    eo, es, ez = ref.linear_attention_carry_ref(qf, kf, v, s0, z0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(eo), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(es), atol=2e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(ez), atol=2e-5)


def test_carry_kernel_zero_state_matches_fresh_kernel():
    """Seeding with zeros is the fresh-sequence (training) kernel; the
    two sum the denominator in different orders."""
    qf, kf, v, _, _ = _carry_inputs(2, 48, 16, 8, seed=3)
    s0 = jnp.zeros((2, 16, 8))
    z0 = jnp.zeros((2, 16))
    out, _, _ = linear_attention_causal_carry_fwd(
        qf, kf, v, s0, z0, chunk=16, interpret=True)
    fresh = _fwd(qf, kf, v, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(fresh),
                               atol=2e-5)


def test_carry_kernel_chained_chunks_match_single_pass():
    """Splitting a prompt into resumed chunks reproduces one full pass —
    the property the chunked-prefill scheduler rests on."""
    qf, kf, v, _, _ = _carry_inputs(2, 40, 16, 8, seed=5)
    s = jnp.zeros((2, 16, 8))
    z = jnp.zeros((2, 16))
    outs = []
    for lo, hi in ((0, 16), (16, 27), (27, 40)):   # uneven chunk schedule
        o, s, z = linear_attention_causal_carry_fwd(
            qf[:, lo:hi], kf[:, lo:hi], v[:, lo:hi], s, z,
            chunk=16, interpret=True)
        outs.append(o)
    full, sf, zf = ref.linear_attention_carry_ref(
        qf, kf, v, jnp.zeros((2, 16, 8)), jnp.zeros((2, 16)))
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)),
                               np.asarray(full), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sf), atol=2e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(zf), atol=2e-5)


def test_jnp_carry_oracle_matches_masked_ref():
    """The pure-jnp chunked carry (core.linear_attention) agrees with the
    O(L^2) masked oracle on out and final state."""
    qf, kf, v, s0, z0 = _carry_inputs(2, 29, 16, 8, seed=7)
    out, s, z = la.linear_attention_causal_carry(qf, kf, v, s0, z0,
                                                 chunk=8)
    eo, es, ez = ref.linear_attention_carry_ref(qf, kf, v, s0, z0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(eo), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(es), atol=2e-5)
    np.testing.assert_allclose(np.asarray(z), np.asarray(ez), atol=2e-5)


def test_jnp_blockwise_matches_masked_ref():
    """The loop-free chunked form (the training forward) agrees with the
    O(L^2) masked oracle across several chunks and a padded tail."""
    qf, kf, v, _, _ = _carry_inputs(2, 29, 16, 8, seed=8)
    out = la.linear_attention_causal_blockwise(qf, kf, v, chunk=8)
    expect = ref.linear_attention_causal_ref(qf, kf, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5)
