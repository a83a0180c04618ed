"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward/train step on CPU, assert shapes + no NaNs; decoder
archs additionally check prefill/decode consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as cfgs
from repro.models import lm


def _batch_for(cfg, B=2, L=16):
    key = jax.random.PRNGKey(1)
    if cfg.modality == "audio":
        return {
            "frames": jax.random.normal(key, (B, L, cfg.d_model)),
            "mask": jax.random.bernoulli(jax.random.fold_in(key, 1), 0.4,
                                         (B, L)),
            "labels": jax.random.randint(jax.random.fold_in(key, 2),
                                         (B, L), 0, cfg.vocab)}
    if cfg.modality == "vlm":
        toks = jax.random.randint(key, (B, L), 0, cfg.vocab)
        return {"tokens": toks, "labels": jnp.roll(toks, -1, 1),
                "patch_embeds": 0.02 * jax.random.normal(
                    jax.random.fold_in(key, 3),
                    (B, cfg.num_patches, cfg.d_model))}
    toks = jax.random.randint(key, (B, L), 0, cfg.vocab)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}


@pytest.mark.parametrize("arch", cfgs.ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = cfgs.get_config(arch, reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch_for(cfg)
    logits, aux = lm.forward_train(params, cfg, batch)
    L = batch["labels"].shape[1]
    exp_positions = L + (cfg.num_patches if cfg.modality == "vlm" else 0)
    assert logits.shape == (2, exp_positions, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    loss, metrics = lm.loss_fn(params, cfg, batch)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: lm.loss_fn(p, cfg, batch)[0])(params)
    gn = sum(float(jnp.sum(jnp.abs(g)))
             for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", [a for a in cfgs.ARCHS
                                  if cfgs.get_config(a, reduced=True).causal])
def test_smoke_prefill_decode_consistency(arch):
    cfg = cfgs.get_config(arch, reduced=True)
    B, L = 2, 12
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    batch = _batch_for(cfg, B, L)
    logits_full, _ = lm.forward_train(params, cfg, batch)
    half = L // 2
    pre_batch = {k: (v[:, :half] if k in ("tokens", "labels") else v)
                 for k, v in batch.items() if k != "labels"}
    lg, st = lm.prefill(params, cfg, pre_batch, max_len=L + 4)
    np.testing.assert_allclose(
        np.asarray(lg[:, 0]),
        np.asarray(logits_full[:, (cfg.num_patches if cfg.modality == "vlm"
                                   else 0) + half - 1]),
        atol=0.05, rtol=0.05)
    maxerr = 0.0
    for t in range(half, L):
        lg, st = lm.decode_step(params, cfg, batch["tokens"][:, t], st)
        tgt = logits_full[:, (cfg.num_patches if cfg.modality == "vlm"
                              else 0) + t]
        maxerr = max(maxerr, float(jnp.abs(lg - tgt).max()))
    assert maxerr < 0.08, f"decode drift {maxerr}"


@pytest.mark.parametrize("arch", cfgs.ASSIGNED)
def test_full_config_geometry(arch):
    """The FULL configs match the assigned table exactly (no allocation:
    eval_shape only)."""
    table = {
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256_000),
        "smollm-135m": (30, 576, 9, 3, 1536, 49_152),
        "granite-8b": (36, 4096, 32, 8, 14_336, 49_152),
        "qwen3-32b": (64, 5120, 64, 8, 25_600, 151_936),
        "yi-34b": (60, 7168, 56, 8, 20_480, 64_000),
        "rwkv6-7b": (32, 4096, 64, 64, 14_336, 65_536),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49_155),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151_936),
        "internvl2-76b": (80, 8192, 64, 8, 28_672, 128_256),
        "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
    }
    cfg = cfgs.get_config(arch)
    nl, dm, nh, nkv, dff, vocab = table[arch]
    assert cfg.n_layers == nl and cfg.d_model == dm
    assert cfg.n_heads == nh and cfg.n_kv == nkv
    assert cfg.d_ff == dff and cfg.vocab == vocab
    pshape = jax.eval_shape(
        lambda k: lm.init_params(k, cfg), jax.random.PRNGKey(0))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(pshape))
    expected_scale = {
        "recurrentgemma-2b": 2.7e9, "smollm-135m": 1.35e8,
        "granite-8b": 8e9, "qwen3-32b": 3.2e10, "yi-34b": 3.4e10,
        "rwkv6-7b": 7e9, "granite-moe-3b-a800m": 3.3e9,
        "qwen3-moe-235b-a22b": 2.35e11, "internvl2-76b": 7e10,
        "hubert-xlarge": 1e9}[arch]
    assert 0.4 * expected_scale < n_params < 2.6 * expected_scale, \
        f"{arch}: {n_params/1e9:.2f}B params vs expected ~{expected_scale/1e9:.1f}B"


def test_moe_configs_match_table():
    g = cfgs.get_config("granite-moe-3b-a800m")
    assert g.moe.num_experts == 40 and g.moe.top_k == 8
    q = cfgs.get_config("qwen3-moe-235b-a22b")
    assert q.moe.num_experts == 128 and q.moe.top_k == 8
    assert q.qk_norm


def test_hybrid_pattern_ratio():
    cfg = cfgs.get_config("recurrentgemma-2b")
    kinds = cfg.layer_kinds()
    assert kinds.count("local") * 2 <= kinds.count("rec") + 2
    assert cfg.window == 2048


def test_kernel_switch_is_pure_config_change():
    """Paper finetuning scenario: exact checkpoint -> PRF kernel, same
    params except the feature params appear."""
    cfg_e = cfgs.get_config("smollm-135m", reduced=True)
    cfg_e = cfgs.darkify(cfg_e, "exact")
    cfg_d = cfgs.darkify(cfg_e, "darkformer", 32)
    p_e = lm.init_params(jax.random.PRNGKey(0), cfg_e)
    p_d = lm.init_params(jax.random.PRNGKey(0), cfg_d)
    leaves_e = {jax.tree_util.keystr(k)
                for k, _ in jax.tree_util.tree_flatten_with_path(p_e)[0]}
    leaves_d = {jax.tree_util.keystr(k)
                for k, _ in jax.tree_util.tree_flatten_with_path(p_d)[0]}
    extra = leaves_d - leaves_e
    assert extra and all("feat" in k for k in extra)
