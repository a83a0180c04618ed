"""End-to-end Pallas path: the full model with use_kernel=True must match
the pure-jnp path (forward + gradients) — proves the kernels integrate at
the framework level, not just in isolation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import configs as cfgs
from repro.models import lm, layers as ll


def test_model_with_pallas_attention_matches_jnp():
    cfg = cfgs.get_config("smollm-135m", reduced=True)
    cfg_k = dataclasses.replace(cfg, use_kernel=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
    l1, m1 = lm.loss_fn(params, cfg, batch)
    l2, m2 = lm.loss_fn(params, cfg_k, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    g1 = jax.grad(lambda p: lm.loss_fn(p, cfg, batch)[0])(params)
    g2 = jax.grad(lambda p: lm.loss_fn(p, cfg_k, batch)[0])(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4)


def test_model_pallas_prefill_matches():
    cfg = cfgs.get_config("smollm-135m", reduced=True)
    cfg_k = dataclasses.replace(cfg, use_kernel=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    l1, s1 = lm.prefill(params, cfg, {"tokens": toks}, max_len=32)
    l2, s2 = lm.prefill(params, cfg_k, {"tokens": toks}, max_len=32)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-3)


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 1000), st.integers(1, 4), st.sampled_from([1, 2, 4]))
def test_moe_output_in_expert_span(seed, top_k, e_div):
    """Property: each token's MoE output is a convex combination (gates sum
    to 1) of per-expert outputs — outputs stay bounded by the max
    expert-output norm."""
    e = 4 * e_div
    cfg = ll.MoEConfig(num_experts=e, top_k=top_k, d_ff=8)
    p = ll.moe_init(jax.random.PRNGKey(seed), 8, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 12, 8))
    out, aux, _ = ll.moe_apply(p, x, cfg)
    assert bool(jnp.all(jnp.isfinite(out)))
    # bound: ||out_t|| <= max_e ||f_e(x_t)||
    def expert_out(xt, ei):
        h = jax.nn.silu(xt @ p["w_gate"][ei]) * (xt @ p["w_up"][ei])
        return h @ p["w_out"][ei]
    norms = []
    for ei in range(e):
        eo = jax.vmap(lambda xt: expert_out(xt, ei))(x[0])
        norms.append(jnp.linalg.norm(eo, axis=-1))
    max_norm = jnp.max(jnp.stack(norms), axis=0)
    out_norm = jnp.linalg.norm(out[0], axis=-1)
    assert bool(jnp.all(out_norm <= max_norm + 1e-4))


@settings(deadline=None, max_examples=8)
@given(st.integers(0, 1000))
def test_adamw_update_invariant_to_param_tree_structure(seed):
    """Property: optimizer treats tree structure transparently — updating
    {'a': w} equals updating {'nested': {'x': w}} leaf-wise."""
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    cfg = AdamWConfig(lr=0.01)
    w = jax.random.normal(jax.random.PRNGKey(seed), (4, 4))
    g = jax.random.normal(jax.random.PRNGKey(seed + 1), (4, 4))
    p1, s1 = {"a": w}, adamw_init({"a": w}, cfg)
    p2, s2 = {"n": {"x": w}}, adamw_init({"n": {"x": w}}, cfg)
    n1, _, _ = adamw_update(p1, {"a": g}, s1, cfg, 0.01)
    n2, _, _ = adamw_update(p2, {"n": {"x": g}}, s2, cfg, 0.01)
    np.testing.assert_allclose(np.asarray(n1["a"]),
                               np.asarray(n2["n"]["x"]))
