"""Random weights made by the benchmark from the seed, on the device, in
one jitted call, in the dtypes the program trains them in.

The tree's layout (which leaves exist, their shapes and dtypes) is the
program's parameter layout, read with ``jax.eval_shape`` — no value the
program computes is used. Values follow the usual initialisation of such
a model: matrices truncated-normal with variance 1/fan_in, norm scales
near 0 (the program's norms scale by 1 + s), the PRF projection W
standard normal, and the DARKFormer re-embedding M the identity plus a
small perturbation, so that M takes part in every feature.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import traffic


def jax_key(seed: int):
    return jax.random.PRNGKey(int(traffic.seed_words(seed, 1)[0] >> 1))


def _leaf(key, path: str, sd):
    shape, dtype = sd.shape, sd.dtype
    if path.endswith("['scale']"):
        return (0.05 * jax.random.normal(key, shape)).astype(dtype)
    if path.endswith("['w']"):                       # PRF projection
        return jax.random.normal(key, shape).astype(dtype)
    if path.endswith("['m_mat']"):
        r, d = shape[-2:]
        eye = jnp.eye(r, d, dtype=jnp.float32)
        noise = jax.random.normal(key, shape) * (0.1 / d ** 0.5)
        return (eye + noise).astype(dtype)
    if path.endswith("['bias']"):
        return jnp.zeros(shape, dtype)
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = fan_in ** -0.5
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)
            ).astype(dtype)


def make(cfg, seed: int):
    """The parameter tree of ``cfg``'s model, from ``seed``."""
    from repro.models import lm
    shapes = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(
            tdef, [_leaf(k, jax.tree_util.keystr(p), sd)
                   for k, (p, sd) in zip(keys, flat)])

    return jax.jit(build)(jax_key(seed))
