"""Layer substrate: norms, rope, mlp, MoE."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.models import layers as ll


def test_rmsnorm_unit_rms():
    x = 5.0 * jax.random.normal(jax.random.PRNGKey(0), (4, 32))
    p = ll.rmsnorm_init(32)
    y = ll.rmsnorm(p, x)
    rms = jnp.sqrt(jnp.mean(jnp.square(y), axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, atol=1e-3)


def test_layernorm_moments():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32)) * 3 + 2
    p = ll.layernorm_init(32)
    y = ll.layernorm(p, x)
    np.testing.assert_allclose(np.asarray(jnp.mean(y, -1)), 0.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.std(y, -1)), 1.0, atol=1e-2)


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 1000), st.integers(1, 32))
def test_rope_preserves_norm_and_relative_angle(seed, shift):
    """RoPE is orthogonal per position, and q.k depends only on the
    relative position (shift both -> same inner product)."""
    key = jax.random.PRNGKey(seed)
    d = 16
    q = jax.random.normal(key, (1, 8, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, d))
    pos = jnp.arange(8)
    q1 = ll.apply_rope(q, pos)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(q1, axis=-1)),
                               np.asarray(jnp.linalg.norm(q, axis=-1)),
                               rtol=1e-4)
    k1 = ll.apply_rope(k, pos)
    q2 = ll.apply_rope(q, pos + shift)
    k2 = ll.apply_rope(k, pos + shift)
    ip1 = jnp.einsum("bld,bld->bl", q1, k1)
    ip2 = jnp.einsum("bld,bld->bl", q2, k2)
    np.testing.assert_allclose(np.asarray(ip1), np.asarray(ip2), atol=1e-3)


@pytest.mark.parametrize("kind", ["swiglu", "geglu", "gelu"])
def test_mlp_kinds(kind):
    p = ll.mlp_init(jax.random.PRNGKey(0), 16, 32, kind)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    y = ll.mlp_apply(p, x, kind)
    assert y.shape == x.shape
    assert not bool(jnp.isnan(y).any())


def _moe_dense(p, x, cfg):
    """The expert layer written plainly: each held expert's SwiGLU over
    every token, weighted by the token's gate for it (0 where the token
    was not routed there)."""
    lo, hi = cfg.held_range
    logits = jnp.einsum("bld,de->ble", x, p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    top, idx = jax.lax.top_k(logits, cfg.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(x)
    for e in range(lo, hi):
        w = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        h = jax.nn.silu(x @ p["w_gate"][e - lo]) * (x @ p["w_up"][e - lo])
        out = out + w[..., None] * (h @ p["w_out"][e - lo])
    return out, idx


def _moe_case(skew, held=None, seed=0):
    """A granite-shaped expert layer at small widths (40 experts, top 8);
    with ``skew`` every token is routed to the same 8 experts."""
    cfg = ll.MoEConfig(num_experts=40, top_k=8, d_ff=8, held=held)
    d = 16
    p = ll.moe_init(jax.random.PRNGKey(seed), d,
                    dataclasses.replace(cfg, held=None))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, d))
    if skew:
        hot = jnp.array([3, 7, 11, 19, 23, 29, 31, 37])
        col = jnp.full((40,), -1.0).at[hot].set(1.0 + 0.1 * jnp.arange(8))
        p["router"] = jnp.broadcast_to(col, (d, 40)) * 1.0
        x = jnp.abs(x)
    if held is not None:
        lo, hi = held
        p = dict(p, **{k: p[k][lo:hi] for k in ("w_gate", "w_up",
                                                  "w_out")})
    return cfg, p, x


@pytest.mark.parametrize("skew", [False, True], ids=["spread", "skewed"])
def test_moe_matches_dense_when_capacity_ample(skew):
    """Dropless routing equals the plain dense-over-experts layer, also
    when every token goes to the same 8 of 40 experts, and the load
    counter equals what the router assigned."""
    cfg, p, x = _moe_case(skew)
    out, aux, counters = ll.moe_apply(p, x, cfg)
    expect, idx = _moe_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5)
    assert float(aux) > 0
    np.testing.assert_array_equal(
        np.asarray(counters["load"]),
        np.bincount(np.asarray(idx).ravel(), minlength=40))
    assert int(counters["away"]) == 0
    if skew:
        assert int(jnp.sum(counters["load"] > 0)) == 8


@pytest.mark.parametrize("skew", [False, True], ids=["spread", "skewed"])
def test_moe_expert_halves_sum_to_the_whole(skew):
    """Two devices holding experts [0, 20) and [20, 40) give outputs that
    sum to the whole layer's, and their counters split its pairs."""
    cfg, p, x = _moe_case(skew)
    whole, _, cw = ll.moe_apply(p, x, cfg)
    parts = []
    for held in ((0, 20), (20, 40)):
        cfg_h, p_h, _ = _moe_case(skew, held)
        parts.append(ll.moe_apply(p_h, x, cfg_h))
    np.testing.assert_allclose(np.asarray(parts[0][0] + parts[1][0]),
                               np.asarray(whole), atol=1e-5)
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(c["load"]) for _, _, c in parts]),
        np.asarray(cw["load"]))
    n = x.shape[0] * x.shape[1] * cfg.top_k
    for _, _, c in parts:
        assert int(jnp.sum(c["load"])) + int(c["away"]) == n


@pytest.mark.parametrize("skew", [False, True], ids=["spread", "skewed"])
def test_moe_gradients_match_dense(skew):
    """Gradients to the router, the experts and the input equal those of
    the dense-over-experts layer."""
    cfg, p, x = _moe_case(skew)

    def loss(f):
        def inner(pp, xx):
            y = f(pp, xx)
            return jnp.sum(y * jnp.sin(jnp.arange(y.size).reshape(y.shape)))
        return jax.grad(inner, argnums=(0, 1))(p, x)
    got = loss(lambda pp, xx: ll.moe_apply(pp, xx, cfg)[0])
    want = loss(lambda pp, xx: _moe_dense(pp, xx, cfg)[0])
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_moe_rows_no_group_covers_never_reach_the_result(monkeypatch):
    """The grouped matmuls leave the rows of pairs routed away undefined
    (on a TPU they hold whatever was there): with those rows NaN, the
    output and every gradient of a half-held layer are still the
    layer's own."""
    cfg, p, x = _moe_case(False, held=(0, 20))
    orig = jax.lax.ragged_dot

    def nan_rows(a, group_sizes):
        rows = jnp.arange(a.shape[0])[:, None] >= jnp.sum(group_sizes)
        return jnp.where(rows, jnp.nan, a)

    @jax.custom_vjp
    def undefined_rows(lhs, rhs, group_sizes):
        return nan_rows(orig(lhs, rhs, group_sizes), group_sizes)

    def fwd(lhs, rhs, group_sizes):
        out, vjp = jax.vjp(lambda a, b: orig(a, b, group_sizes), lhs, rhs)
        return nan_rows(out, group_sizes), (vjp, group_sizes)

    def bwd(res, ct):
        vjp, group_sizes = res
        d_lhs, d_rhs = vjp(ct)
        return nan_rows(d_lhs, group_sizes), d_rhs, None

    undefined_rows.defvjp(fwd, bwd)

    def run():
        return jax.value_and_grad(
            lambda pp, xx: jnp.sum(ll.moe_apply(pp, xx, cfg)[0] ** 2),
            argnums=(0, 1))(p, x)
    want = run()
    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_rows)
    got = run()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_moe_grads_flow_to_router_and_experts():
    cfg = ll.MoEConfig(num_experts=4, top_k=2, d_ff=8)
    p = ll.moe_init(jax.random.PRNGKey(0), 8, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8))
    g = jax.grad(lambda pp: ll.moe_apply(pp, x, cfg)[0].sum()
                 + ll.moe_apply(pp, x, cfg)[1])(p)
    for k in ("router", "w_gate", "w_up", "w_out"):
        assert float(jnp.abs(g[k]).max()) > 0
