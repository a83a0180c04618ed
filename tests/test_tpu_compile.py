"""Ahead-of-time compiles for a described TPU v5e at smollm-135m widths.

Interpret mode checks none of what Mosaic enforces: the (8, 128) block
tiling rule, the primitives a kernel body may use, and the VMEM limit.
These tests lower every Pallas kernel with ``interpret=False`` against a
``v5e:2x2`` topology that is described, not attached, and let the TPU
compiler accept or refuse it; the last one compiles a whole fused decode
step of the served model. Nothing runs, so they say nothing about
results or times (``chip_smoke.py`` does that on a chip).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers all import
this file.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs as cfgs
from repro.kernels import ops
from repro.kernels.linear_attn_scan import (
    linear_attention_causal_carry_fwd, linear_attention_causal_fwd)
from repro.kernels.prf_decode_step import prf_decode_step_fwd
from repro.kernels.prf_featmap import prf_featmap_fwd
from repro.kernels.prf_fused_decode import prf_fused_decode_fwd
from repro.kernels.prf_fused_prefill import prf_fused_prefill_fwd
from repro.kernels.wkv6_scan import wkv6_fwd
from repro.models import lm

# smollm-135m attention geometry (configs/smollm_135m.py): 9 query heads
# in 3 KV groups, d_head 64, darkformer m=256 features of rank r = d.
G, HG, D, M = 3, 3, 64, 256
SLOTS, ROWS, L = 16, 8, 512
N = SLOTS * G * HG


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("dark", [True, False], ids=["dark", "iso"])
def test_prf_fused_decode_compiles(one_chip, dark):
    def f(q, k, v, a, m_mat, s, z, c):
        return prf_fused_decode_fwd(q, k, v, a, m_mat if dark else None,
                                    s, z, c)
    _compile(f, one_chip, ((SLOTS, G, HG, D), BF16), ((SLOTS, G, D), BF16),
             ((SLOTS, G, D), F32), ((G, D, M), F32), ((G, D, D), F32),
             ((SLOTS, G, HG, M, D), F32), ((SLOTS, G, HG, M), F32),
             ((SLOTS, G), F32))


@pytest.mark.parametrize("dark", [True, False], ids=["dark", "iso"])
def test_prf_fused_prefill_compiles(one_chip, dark):
    def f(q, k, v, a, m_mat, s, z, c, vl):
        return prf_fused_prefill_fwd(q, k, v, a, m_mat if dark else None,
                                     s, z, c, vl)
    _compile(f, one_chip, ((ROWS, G, HG, L, D), BF16),
             ((ROWS, G, L, D), BF16), ((ROWS, G, L, D), BF16),
             ((G, D, M), F32), ((G, D, D), F32),
             ((ROWS, G, HG, M, D), F32), ((ROWS, G, HG, M), F32),
             ((ROWS, G), F32), ((ROWS,), jnp.int32))


def test_linear_attention_causal_compiles(one_chip):
    _compile(linear_attention_causal_fwd, one_chip, ((N, L, M), F32),
             ((N, L, M), F32), ((N, L, D), F32))


def test_linear_attention_carry_compiles(one_chip):
    _compile(linear_attention_causal_carry_fwd, one_chip,
             ((N, L, M), F32), ((N, L, M), F32), ((N, L, D), F32),
             ((N, M, D), F32), ((N, M), F32))


@pytest.mark.parametrize("dark", [True, False], ids=["dark", "iso"])
def test_prf_featmap_compiles(one_chip, dark):
    def f(x, m_mat, w, c):
        return prf_featmap_fwd(x, m_mat if dark else None, w, c)
    _compile(f, one_chip, ((N * 8, D), F32), ((D, D), F32),
             ((M, D), F32), ((), F32))


def test_prf_decode_step_compiles(one_chip):
    _compile(prf_decode_step_fwd, one_chip, ((N, M), F32), ((N, M), F32),
             ((N, D), F32), ((N, M, D), F32), ((N, M), F32), ((N, 1), F32))


def test_wkv6_compiles(one_chip):
    # RWKV-6 heads are 64 wide for every size
    _compile(wkv6_fwd, one_chip, ((N, L, 64), F32), ((N, L, 64), F32),
             ((N, L, 64), F32), ((N, L, 64), F32), ((64,), F32))


def test_fused_decode_step_compiles(one_chip, monkeypatch):
    """One served decode step of smollm-135m at full width (depth cut to
    two layers, the scanned body is the same) takes the fused kernel."""
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    cfg = dataclasses.replace(cfgs.get_config("smollm-135m"), n_layers=2,
                              use_kernel=True)
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    state = jax.eval_shape(lambda: lm.init_serve_state(
        cfg, b=SLOTS, max_len=1024, per_slot=True, stacked=True))

    def step(params, state, toks):
        proj = lm.build_decode_proj(params, cfg, stacked=True)
        p = dict(params, layers=lm.stack_layer_params(params, cfg))
        return lm.decode_step(p, cfg, toks, state, proj=proj)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)
    toks = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(step).lower(place(params), place(state),
                                   toks).compile()
    assert "tpu_custom_call" in compiled.as_text()
