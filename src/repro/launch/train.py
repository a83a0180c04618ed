"""Training launcher (end-to-end driver, deliverable (b)).

Runs real training on whatever devices exist (CPU here; the same code path
works under a TPU mesh — the mesh/sharding logic is shared with dryrun.py).
Features: pjit + sharding rules, checkpoint/restart via TrainSupervisor,
failure injection, preemption handling, the paper's finetuning modes
(--finetune-from, --qkv-only), and kernel switching (--kernel).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduced --steps 200 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch darkformer-2b \
      --reduced --kernel performer --steps 100
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp

from repro import configs as cfgs
from repro.data import SyntheticLM, SyntheticAudio, SyntheticVLM, C4Mock
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import setup_compile_cache
from repro.models import lm
from repro.optim import AdamWConfig, adamw_init
from repro.optim.schedules import cosine_warmup
from repro.parallel import (param_specs, opt_state_specs, batch_specs,
                            make_shardings)
from repro.runtime import TrainSupervisor, StragglerMonitor, \
    PreemptionHandler
from repro import checkpoint as ckpt_lib


def make_data(cfg, args):
    if cfg.modality == "audio":
        return SyntheticAudio(cfg.d_model, args.seq, args.batch,
                              vocab=cfg.vocab, seed=args.seed)
    if cfg.modality == "vlm":
        return SyntheticVLM(cfg.d_model, cfg.num_patches, args.seq,
                            args.batch, cfg.vocab, seed=args.seed)
    if args.data == "c4mock":
        return C4Mock(cfg.vocab, args.seq, args.batch, seed=args.seed)
    return SyntheticLM(cfg.vocab, args.seq, args.batch, seed=args.seed)


def main(argv: list[str] | None = None) -> tuple[dict, list[dict]]:
    """Train as the CLI arguments say. Returns the final
    {"params", "opt"} state and the logged metrics (one dict per
    ``--log-every`` step, and the last step)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-runnable)")
    ap.add_argument("--kernel", default=None,
                    help="override attention kernel "
                         "(exact|performer|darkformer|lfk|random|constant)")
    ap.add_argument("--features", type=int, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", default="synthetic",
                    choices=["synthetic", "c4mock"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--simulate-failure-at", type=int, default=None)
    ap.add_argument("--finetune-from", default=None,
                    help="checkpoint dir with pretrained params")
    ap.add_argument("--qkv-only", action="store_true",
                    help="paper Fig.4: train only q/k/v + PRF covariance")
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = cfgs.get_config(args.arch, reduced=args.reduced)
    if args.kernel:
        cfg = cfgs.darkify(cfg, args.kernel,
                           args.features or cfg.attn.num_features)
    mesh = mesh_lib.make_local_mesh(args.mesh_data, args.mesh_model)

    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.finetune_from:
        # supervisor checkpoints store {"params", "opt"}; restore params
        # only (fresh optimizer for the finetune phase).
        wrapped, step0 = ckpt_lib.restore_checkpoint(
            args.finetune_from, {"params": params})
        params = wrapped["params"]
        print(f"finetuning from {args.finetune_from} @ step {step0}")
    opt_cfg = AdamWConfig(lr=args.lr)
    opt_state = adamw_init(params, opt_cfg)

    pspecs = param_specs(params, mesh, moe=cfg.moe is not None)
    pshard = make_shardings(pspecs, mesh)
    oshard = make_shardings(
        opt_state_specs(opt_state, pspecs, mesh), mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, pshard)
    opt_state = jax.tree_util.tree_map(jax.device_put, opt_state, oshard)

    schedule = cosine_warmup(args.lr, args.warmup, args.steps)
    freeze = steps_lib.qkv_only_freeze if args.qkv_only else None
    raw_step = steps_lib.make_train_step(cfg, opt_cfg, schedule, freeze)
    data = make_data(cfg, args)
    batch0 = data.batch(0)
    bshard = make_shardings(batch_specs(batch0, mesh), mesh)
    jitted = jax.jit(raw_step,
                     in_shardings=(pshard, oshard, bshard, None),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))

    metrics_log = []

    def step_fn(state, step):
        params, opt_state = state["params"], state["opt"]
        batch = jax.tree_util.tree_map(
            jax.device_put, dict(data.batch(step)), bshard)
        params, opt_state, metrics = jitted(params, opt_state, batch,
                                            jnp.int32(step))
        state = {"params": params, "opt": opt_state}
        if step % args.log_every == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = step
            metrics_log.append(m)
            print(f"step {step:5d} loss {m['loss']:.4f} "
                  f"acc {m['accuracy']:.4f} gnorm {m['grad_norm']:.3f}",
                  flush=True)
        return state

    state = {"params": params, "opt": opt_state}
    t0 = time.time()
    if args.ckpt_dir:
        sup = TrainSupervisor(args.ckpt_dir, ckpt_every=args.ckpt_every,
                              monitor=StragglerMonitor(),
                              preemption=PreemptionHandler())
        state = sup.run(state, step_fn, args.steps,
                        fail_at=args.simulate_failure_at)
        if sup.monitor.straggler_steps:
            print(f"stragglers flagged: {sup.monitor.straggler_steps}")
    else:
        for step in range(args.steps):
            state = step_fn(state, step)
    dt = time.time() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.2f} steps/s)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(metrics_log, f, indent=1)
    return state, metrics_log


if __name__ == "__main__":
    main()
