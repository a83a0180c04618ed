"""The training step's layer scopes reach the compiled program.

The device trace names each op by its HLO ``op_name``; the benchmark
reads per-layer step time from the ``jax.named_scope`` components of
that name (``chipbench/scopes.py``). These tests compile the reduced
smollm darkformer train step as ``make_train_step`` builds it, with the
layer scan rematerialized as the benchmark cell runs it, and read the
``op_name`` metadata of the compiled HLO.
"""
import dataclasses
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import configs as cfgs
from repro.core import scopes
from repro.launch import steps
from repro.models import lm
from repro.optim import AdamWConfig, adamw_init
from repro.optim.schedules import cosine_warmup


def _scopes_in(op_name: str) -> list[str]:
    """The scope components of an op name, bare or inside transform
    wrappers such as ``transpose(jvp(mlp))``."""
    out = []
    for part in op_name.rstrip(":").split("/"):
        inner = re.sub(r"^(?:[\w\-]+\()*([^()]*)\)*$", r"\1", part)
        if inner in scopes.STEP_SCOPES:
            out.append(inner)
    return out


@pytest.fixture(scope="module")
def op_names():
    cfg = dataclasses.replace(cfgs.get_config("smollm-135m", reduced=True),
                              remat="dots")
    assert cfg.attn.kind == "darkformer"
    opt = AdamWConfig()
    step = steps.make_train_step(cfg, opt, cosine_warmup(3e-4, 20, 10000))
    params = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    state = jax.eval_shape(lambda p: adamw_init(p, opt), params)
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, state, {"tokens": tok, "labels": tok},
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    return re.findall(r'op_name="([^"]*)"', compiled.as_text())


@pytest.mark.parametrize("scope", scopes.STEP_SCOPES)
def test_every_scope_is_in_the_compiled_step(op_names, scope):
    assert any(scope in _scopes_in(n) for n in op_names)


@pytest.mark.parametrize("scope", (scopes.PRF_FEATURES, scopes.PRF_MIX))
def test_prf_scopes_cover_the_backward_pass(op_names, scope):
    assert any("transpose(" in n and scope in _scopes_in(n)
               for n in op_names)


def test_scopes_never_nest(op_names):
    nested = [n for n in op_names if len(_scopes_in(n)) > 1]
    assert not nested, nested[:5]


_COMPILE_SCOPED = """
import sys, jax, jax.numpy as jnp
from repro.launch.compile_cache import setup_compile_cache
setup_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) * 2.0
print(jax.jit(f).lower(jnp.ones(8)).compile().as_text())
"""


def test_a_cached_step_keeps_its_own_scopes(tmp_path):
    """The persistent cache serves no entry compiled under other scopes
    (its ops would be profiled under their names), and the same source
    still hits its own entry in a later process."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))

    def compile_under(scope):
        out = subprocess.run(
            [sys.executable, "-c", _COMPILE_SCOPED, scope], env=env,
            capture_output=True, text=True, timeout=300, check=True).stdout
        return out, len(list(tmp_path.iterdir()))

    text, n_first = compile_under("alpha")
    assert "alpha/" in text and n_first > 0
    text, n_other = compile_under("beta")
    assert "beta/" in text and "alpha/" not in text
    assert n_other > n_first
    text, n_again = compile_under("alpha")
    assert "alpha/" in text and n_again == n_other
