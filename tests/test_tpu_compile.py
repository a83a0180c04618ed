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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs as cfgs
from repro.kernels import ops
from repro.kernels.linear_attn_scan import (
    linear_attention_causal_carry_fwd, prf_mix_fwd)
from repro.kernels.prf_decode_step import prf_decode_step_fwd
from repro.kernels.prf_featmap import prf_featmap_fwd
from repro.kernels.prf_fused_decode import prf_fused_decode_fwd
from repro.kernels.prf_fused_prefill import prf_fused_prefill_fwd
from repro.kernels.wkv6_scan import wkv6_fwd
from repro.launch import steps as steps_lib
from repro.models import lm
from repro.optim import AdamWConfig, adamw_init

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
    _compile(prf_mix_fwd, one_chip, ((ROWS, G, HG, L, M), BF16),
             ((ROWS, G, L, M), BF16), ((ROWS, L, G * D), BF16))


# the training mix's Pallas pair at the widths that run it: smollm-135m
# (3 groups of 3 heads, d_head 64, 2048-token rows), granite-8b (4 heads
# a group, d_head 128) and granite-moe-3b-a800m (8 groups of 3 heads,
# d_head 64, 4096-token rows), m = 256; both kernels of the backward too
@pytest.mark.parametrize("g,hg,dv,l", [(G, HG, D, 2048), (G, 4, 128, 2048),
                                       (8, 3, 64, 4096)],
                         ids=["smollm", "granite", "granitemoe"])
def test_prf_mix_pair_compiles(one_chip, monkeypatch, g, hg, dv, l):
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)

    def f(q, k, v, ct):
        out, vjp = jax.vjp(lambda *a: ops.linear_attention_causal(
            *a, eps=1e-30), q, k, v)
        return out, vjp(ct)
    n = 2
    hlo = _compile(f, one_chip, ((n, g, hg, l, M), F32),
                   ((n, g, 1, l, M), F32), ((n, g, 1, l, dv), BF16),
                   ((n, g, hg, l, dv), BF16)).as_text()
    assert {name for name, _ in _kernel_scopes(hlo)} == set(KERNELS)


KERNELS = ("prf_mix_fwd", "prf_mix_bwd_dq", "prf_mix_bwd_dkv")


def _kernel_scopes(hlo: str) -> set:
    """(kernel, enclosing scope) of each Pallas call in ``hlo``, from
    its op_name path: ".../prf_mix/transpose(jvp(prf_mix_bwd_dq))/..."
    names the kernel and the scope just outside it."""
    found = set()
    for path in re.findall(r'custom_call_target="tpu_custom_call".*?'
                           r'op_name="([^"]*)"', hlo):
        parts = [re.sub(r"^(?:[\w\-]+\()*([^()]*)\)*$", r"\1", p)
                 for p in path.split("/")]
        for i, part in enumerate(parts):
            if part in KERNELS:
                found.add((part, parts[i - 1] if i else None))
    return found


def _train_step_hlo(cfg, one_chip, rows, l):
    """The compiled text of ``cfg``'s training step for one v5e chip."""
    opt_cfg = AdamWConfig()
    step = steps_lib.make_train_step(cfg, opt_cfg, lambda s: 1e-4)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    opt = jax.eval_shape(lambda: adamw_init(params, opt_cfg))
    batch = {k: jax.ShapeDtypeStruct((rows, l), jnp.int32,
                                     sharding=one_chip)
             for k in ("tokens", "labels")}
    return jax.jit(step).lower(
        place(params), place(opt), batch,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()


def test_train_step_takes_the_prf_mix_kernels(one_chip, monkeypatch):
    """The smollm-135m training step (value_and_grad through the remat'd
    layer scan, two layers at full width, 2048-token rows) runs its
    causal PRF mix in the Pallas pair, forward and backward, under the
    ``prf_mix`` scope, and holds no (L, L) array."""
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    cfg = dataclasses.replace(cfgs.get_config("smollm-135m"), n_layers=2)
    l = 2048
    hlo = _train_step_hlo(cfg, one_chip, 1, l)
    assert _kernel_scopes(hlo) == {(name, "prf_mix") for name in KERNELS}
    assert not re.search(rf"\[(\d+,)*{l},{l}\]", hlo)


def _routing_row_passes(hlo: str, rows: str) -> list:
    """(op, fused ops) of each top-level op of ``hlo`` that writes the
    expert layer's (token, choice) rows, shape ``rows``, under the
    ``moe_dispatch`` or ``moe_combine`` scope."""
    comps = dict(re.findall(r"\n%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}", hlo,
                            re.S))
    found = []
    for name, body in comps.items():
        if "fused" in name:
            continue
        for line in body.split("\n"):
            m = re.match(rf"\s*(?:ROOT )?%?([\w.\-]+) = bf16\[{rows}\]\S* "
                         r"(fusion|custom-call|reduce-precision|copy)\(", line)
            if m and re.search(r'op_name="[^"]*/moe_(?:dispatch|combine)/',
                               line):
                calls = re.search(r"calls=%?([\w.\-]+)", line)
                fused = set(re.findall(
                    r"= \S+ (gather|select|broadcast|reduce-precision|add|"
                    r"multiply)\(", comps[calls.group(1)])) if calls else None
                found.append((m.group(1), fused or {m.group(2)}))
    return found


def test_granitemoe_train_step_compiles(one_chip, monkeypatch):
    """The granite-moe-3b-a800m training step at the benchmark cell's
    cut (20 of 40 experts held, a 24576-row vocabulary slice, every
    width as published; three of its layers, the scanned body is the
    same)
    on 2 rows of 4096 tokens compiles for a v5e: the PRF mix takes the
    Pallas pair at 8 groups of 3 heads, the experts run as grouped
    matmuls, and it holds no (L, L) array and no per-expert capacity
    buffer (B, E, C, d). The routing writes its 65536 rows of 1536 in
    seven passes a layer: four gathers (dispatch and combine, forward
    and backward), the dispatch's reduce-precision, the backward's
    gated product and its masked add; no broadcast of the token rows to
    their 8 choices, and no mask of its own."""
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    cfg = cfgs.get_config("granite-moe-3b-a800m", n_layers=3, vocab=24576,
                          moe={"held": [0, 20]})
    assert cfg.moe.n_held == 20 and cfg.moe.num_experts == 40
    l = 4096
    hlo = _train_step_hlo(cfg, one_chip, 2, l)
    assert _kernel_scopes(hlo) == {(name, "prf_mix") for name in KERNELS}
    assert "bf16[2,8,3,4096,256]" in hlo      # q features, 8 groups of 3
    assert "ragged-dot" in hlo
    assert not re.search(rf"\[(\d+,)*{l},{l}\]", hlo)
    assert not re.search(r"\[2,(20|40),\d+,1536\]", hlo)
    passes = _routing_row_passes(hlo, "65536,1536")
    assert len(passes) == 7, passes
    assert sum("gather" in ops_ for _, ops_ in passes) == 4, passes
    assert not any(ops_ <= {"select", "broadcast"} for _, ops_ in passes)
    assert not _routing_row_passes(hlo, "8192,8,1536")


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
