"""Shared bench harness: the scaled-down Gemma-style model + train loops.

The paper's experiments (Fig. 2-5) run Gemma-2B on C4; this container is a
single CPU core, so benches run a width/depth-reduced model of the same
family (MQA + GeGLU, embed scaling) on the synthetic bigram corpus, with a
deliberately small feature budget (m = 16) — the regime where sampling
geometry matters. All comparisons are RELATIVE (dark vs performer vs exact
vs baselines), matching the paper's claims rather than its absolute
numbers.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.feature_maps import FeatureConfig
from repro.data import SyntheticLM
from repro.launch import steps as steps_lib
from repro.models import ModelConfig, lm
from repro.optim import AdamWConfig, adamw_init
from repro.optim.schedules import cosine_warmup, constant

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments", "bench")

SEQ = 64
BATCH = 8
VOCAB = 256


def bench_cfg(kernel: str = "darkformer", m: int = 16,
              scan: bool = True) -> ModelConfig:
    return ModelConfig(
        name=f"bench-{kernel}", n_layers=4, d_model=64, n_heads=4, n_kv=1,
        d_head=16, d_ff=192, vocab=VOCAB, mlp_kind="geglu",
        embedding_multiplier=64 ** 0.5, tie_embeddings=True, remat="none",
        scan_layers=scan,
        attn=FeatureConfig(kind=kernel, num_features=m, orthogonal=True))


def transplant(src_params, dst_params):
    """Copy every shared leaf from src to dst (checkpoint surgery for
    kernel switches: exact -> PRF adds feat params, everything else moves)."""
    flat_src = {jax.tree_util.keystr(k): v for k, v in
                jax.tree_util.tree_flatten_with_path(src_params)[0]}
    flat_dst, tdef = jax.tree_util.tree_flatten_with_path(dst_params)
    merged = [flat_src.get(jax.tree_util.keystr(k), v)
              for k, v in flat_dst]
    return jax.tree_util.tree_unflatten(tdef, merged)


def train(cfg: ModelConfig, steps: int, lr: float, *, seed: int = 0,
          params=None, freeze: Optional[Callable] = None,
          record_every: int = 10, warmup: int = 20,
          data: Optional[SyntheticLM] = None,
          eval_batches: int = 2) -> tuple[dict, list[dict]]:
    """Train and record {step, loss, accuracy, eval_accuracy, dt}."""
    data = data or SyntheticLM(cfg.vocab, SEQ, BATCH, seed=7)
    eval_data = SyntheticLM(cfg.vocab, SEQ, BATCH, seed=7, host=13)
    if params is None:
        params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    opt_cfg = AdamWConfig(lr=lr)
    opt = adamw_init(params, opt_cfg)
    sched = cosine_warmup(lr, warmup, steps) if warmup else constant(lr)
    step_fn = jax.jit(steps_lib.make_train_step(cfg, opt_cfg, sched,
                                                freeze))
    eval_fn = jax.jit(steps_lib.make_eval_step(cfg))
    history = []
    t_last = time.time()
    for s in range(steps):
        params, opt, m = step_fn(params, opt, dict(data.batch(s)),
                                 jnp.int32(s))
        if s % record_every == 0 or s == steps - 1:
            accs = [float(eval_fn(params, dict(eval_data.batch(10_000 + i))
                                  )["accuracy"]) for i in range(eval_batches)]
            now = time.time()
            history.append({
                "step": s, "loss": float(m["loss"]),
                "accuracy": float(m["accuracy"]),
                "eval_accuracy": sum(accs) / len(accs),
                "grad_norm": float(m["grad_norm"]),
                "dt": now - t_last})
            t_last = now
    return params, history


def time_call(fn, *args, iters: int = 10, warmup: int = 2) -> float:
    """Median wall time per call in microseconds (jit-compiled fns)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def save_result(name: str, payload: dict) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def load_result(name: str) -> Optional[dict]:
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return None
