"""Step builders shared by train.py / serve.py / dryrun.py."""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import scopes
from repro.models import lm
from repro.optim import AdamWConfig, adamw_init, adamw_update


def make_train_step(cfg: lm.ModelConfig, opt_cfg: AdamWConfig,
                    schedule: Callable, freeze: Optional[Callable] = None):
    """Returns train_step(params, opt_state, batch, step) -> (p, o, metrics).

    ``freeze`` is a predicate over the param tree-path string: True means
    the leaf's gradient is zeroed (the paper's limited-attention finetuning
    freezes everything but q/k/v and the PRF covariance M).
    """

    def train_step(params, opt_state, batch, step):
        rng = jax.random.fold_in(jax.random.PRNGKey(0), step)
        (loss, metrics), grads = jax.value_and_grad(
            lm.loss_fn, has_aux=True)(params, cfg, batch, rng)
        with jax.named_scope(scopes.OPTIMIZER):
            if freeze is not None:
                flat, tdef = jax.tree_util.tree_flatten_with_path(grads)
                flat = [(p, jnp.zeros_like(g)
                         if freeze(jax.tree_util.keystr(p)) else g)
                        for p, g in flat]
                grads = jax.tree_util.tree_unflatten(tdef,
                                                     [g for _, g in flat])
            lr = schedule(step)
            params, opt_state, om = adamw_update(params, grads, opt_state,
                                                 opt_cfg, lr)
        return params, opt_state, {**metrics, **om}

    return train_step


def make_eval_step(cfg: lm.ModelConfig):
    def eval_step(params, batch):
        _, metrics = lm.loss_fn(params, cfg, batch)
        return metrics
    return eval_step


def make_prefill_step(cfg: lm.ModelConfig, max_len: int):
    def prefill_step(params, batch):
        return lm.prefill(params, cfg, batch, max_len)
    return prefill_step


def make_decode_step(cfg: lm.ModelConfig):
    def serve_step(params, token, state):
        return lm.decode_step(params, cfg, token, state)
    return serve_step


# The paper's limited-attention finetuning (Fig. 4): train only q/k/v
# projections and the DARKFormer covariance M (plus the PRF projection W in
# lfk mode).
def qkv_only_freeze(path: str) -> bool:
    keep = ("['wq']", "['wk']", "['wv']", "['m_mat']")
    return not any(k in path for k in keep)
