"""The training cell: the trainer's jitted, donated AdamW step, driven in
a closed loop.

Set-up builds the step and its state once, drives it through the first
``check_steps`` steps (the ones the reference follows), and hands that
same step and state to the window. The window runs steps back to back,
one in flight behind the one the host waits for, and the rate is all
the tokens of the steps completed over the time they took.
"""
from __future__ import annotations

import gc
import statistics
import sys

import jax
import jax.numpy as jnp

from chipbench import core, model, reference, traffic, weights


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree)


@jax.jit
def _diff_norms(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)


@jax.jit
def _cosines(a, b):
    def cos(x, y):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        nx, ny = jnp.sqrt(jnp.sum(x * x)), jnp.sqrt(jnp.sum(y * y))
        return jnp.sum(x * y) / jnp.maximum(nx * ny, 1e-30)
    return jax.tree_util.tree_map(cos, a, b)


def _keyed(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(x) for p, x in flat}


def direction_gap(prog_vec, ref_vec, keep) -> tuple[float, str]:
    """Worst leaf of 1 - cosine between the program's first gradient and
    the reference's, over the leaves in ``keep``."""
    cos = _keyed(_cosines(prog_vec, ref_vec))
    worst, where = 0.0, ""
    for k in keep:
        if 1.0 - cos[k] > worst:
            worst, where = 1.0 - cos[k], k
    return worst, where


def leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Worst leaf of |prog norm - ref norm| / max(ref norm of that leaf,
    median ref norm)."""
    med = statistics.median(ref.values())
    worst, where = 0.0, ""
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        g = abs(prog[k] - r) / max(r, med, 1e-30)
        if g > worst:
            worst, where = g, k
    return worst, where


def build(cell: dict, seed: int):
    """The step, its state and the batch maker, as the trainer makes
    them (AdamW, cosine warm-up schedule, params and optimizer state
    donated)."""
    from repro.launch import steps as steps_lib
    from repro.optim import AdamWConfig, adamw_init
    from repro.optim.schedules import cosine_warmup
    cj, tr = cell["config"], cell["traffic"]
    tc = cj["train"]
    cfg = model.program_config(cj)
    opt_cfg = AdamWConfig(lr=tc["lr"], b1=tc["b1"], b2=tc["b2"],
                          eps=tc["eps"], weight_decay=tc["weight_decay"],
                          grad_clip=tc["grad_clip"])
    schedule = cosine_warmup(tc["lr"], tc["warmup"], tc["schedule_steps"])
    step = jax.jit(steps_lib.make_train_step(cfg, opt_cfg, schedule),
                   donate_argnums=(0, 1))
    params = weights.make(cfg, seed)
    opt = adamw_init(params, opt_cfg)
    feed = traffic.train_rows(tr, seed, cfg.vocab)

    def batch():
        rows = next(feed)
        return rows, {"tokens": jnp.asarray(rows[:, :-1]),
                      "labels": jnp.asarray(rows[:, 1:])}

    return cfg, step, params, opt, batch


def first_steps(cell: dict, seed: int) -> dict:
    """Build the step and its state, and drive it through the steps the
    reference follows. Returns the readings, the rows they ran on, and
    the step and state to go on with. The first gradient, as the
    optimizer got it, is its first moment after one step, kept on the
    host for the check."""
    import numpy as np
    tc = cell["config"]["train"]
    cfg, step, params, opt, batch = build(cell, seed)
    rows_seen, losses, first, first_vec = [], [], None, None
    for s in range(cell["traffic"]["check_steps"]):
        rows, b = batch()
        rows_seen.append(rows)
        params, opt, m = step(params, opt, b, jnp.int32(s))
        losses.append(float(m["loss"]))
        if s == 0:
            first = _keyed(jax.tree_util.tree_map(
                lambda x: x / (1.0 - tc["b1"]), _norms(opt["mu"])))
            first_vec = jax.device_get(opt["mu"])
    w0 = weights.make(cfg, seed)
    change = _keyed(_diff_norms(params, w0))
    del w0
    return {"loss": losses, "grad": first, "grad_vec": first_vec,
            "change": change,
            "rows": np.stack(rows_seen), "cfg": cfg, "step": step,
            "params": params, "opt": opt, "batch": batch}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        clock: core.Clock, device: dict, compiles: core.CompileCounter
        ) -> tuple[dict, list]:
    cj, tr = cell["config"], cell["traffic"]
    st = first_steps(cell, seed)
    step, batch = st["step"], st["batch"]
    params, opt = st.pop("params"), st.pop("opt")
    tokens_per_step = tr["batch"] * tr["seq"]
    s_idx = tr["check_steps"]
    # one more step, blocked on, so the window starts steady
    _, b = batch()
    params, opt, m = step(params, opt, b, jnp.int32(s_idx))
    s_idx += 1
    jax.block_until_ready(m["loss"])
    setup_s = clock()
    c0 = compiles.n
    ann = jax.profiler.TraceAnnotation
    trace_s = min(seconds, tr.get("trace_s", 4.0))
    tdir = core.out_dir() / "trace" / str(seed) if trace else None
    tctx: dict = {}
    if trace:
        jax.profiler.start_trace(str(tdir))
        tctx["ann"] = jax.profiler.TraceAnnotation("window")
        tctx["ann"].__enter__()
    t_start = clock()
    tctx["t0"] = t_start
    done_t, n_done, n_traced = t_start, 0, 0
    inflight = None
    while True:
        _, b = batch()
        with ann("train_step"):
            params, opt, m = step(params, opt, b, jnp.int32(s_idx))
        s_idx += 1
        if inflight is not None:
            jax.block_until_ready(inflight)
            now = clock()
            if now > t_start + seconds:
                break
            n_done += 1
            done_t = now
            if trace and "t1" not in tctx and now >= t_start + trace_s:
                tctx["t1"] = now
                n_traced = n_done
                tctx["ann"].__exit__(None, None, None)
                jax.profiler.stop_trace()
        inflight = m["loss"]
    jax.block_until_ready(m["loss"])
    if trace and "t1" not in tctx:
        tctx["t1"] = clock()
        n_traced = n_done
        tctx["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    win_compiles = compiles.n - c0
    mem = core.memory_peak_bytes(jax, 1)
    rate = n_done * tokens_per_step / max(done_t - t_start, 1e-9)
    print(f"window: {n_done} steps in {done_t - t_start:.3f} s, "
          f"{win_compiles} compiles", file=sys.stderr)
    metrics = {"train_tok_s": rate, "setup_s": setup_s}
    layer_ctx = None
    if trace:
        layer_ctx = {"trace_dir": tdir,
                     "window_s": tctx["t1"] - tctx["t0"],
                     "train_steps": n_traced,
                     "tokens_per_step": tokens_per_step, "config": cj,
                     "traffic": tr}
    del params, opt, m, inflight
    gc.collect()

    # -- correctness: the reference follows the first steps ------------
    t_ref = clock()
    w = weights.make(st["cfg"], seed)
    ref = reference.train_steps(w, model.ref_dims(cj), cj["train"],
                                st["rows"])
    print(f"reference: {clock() - t_ref:.3f} s", file=sys.stderr)
    checks = compare(st, ref, tr["limits"])
    return {"correct": all(c["ok"] for c in checks), "attempted": n_done,
            "failed": 0, "metrics": metrics, "memory_peak_bytes": mem,
            "layer_ctx": layer_ctx,
            "extra": {"window_compiles": win_compiles,
                      "window_steps": n_done}}, checks


NUMBERS = ("loss_gap", "grad_norm_gap", "grad_dir_gap", "change_norm_gap")


def compare(prog: dict, ref: dict, limits: dict) -> list[dict]:
    """The numbers compared with the reference, beside their limits.
    ``prog`` and ``ref`` hold per-step ``loss``, the first gradient
    (``grad_vec``) with its per-leaf norms (``grad``), and the per-leaf
    norms of the parameters' change (``change``)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                       ref["loss"]))
    gmed = statistics.median(ref["grad"].values())
    moving = {k for k, v in ref["grad"].items() if v >= 1e-3 * gmed}
    grad_gap, gwhere = leaf_gap(prog["grad"], ref["grad"])
    dir_gap, dwhere = direction_gap(prog["grad_vec"], ref["grad_vec"],
                                    moving)
    chg_gap, cwhere = leaf_gap(prog["change"], ref["change"], keep=moving)
    print(f"losses program {prog['loss']} reference {ref['loss']}; "
          f"worst grad-norm leaf {gwhere}, worst grad-direction leaf "
          f"{dwhere}, worst change leaf {cwhere}; "
          f"{len(ref['grad']) - len(moving)} leaves without gradient "
          f"left out of the direction and the change", file=sys.stderr)
    out = []
    for name, v in zip(NUMBERS, (loss_gap, grad_gap, dir_gap, chg_gap)):
        if name not in limits:
            # read but not compared (no reading separates it: PERF.md)
            print(f"{name}: {v!r} (not compared)", file=sys.stderr)
            continue
        out.append({"name": name, "value": v, "limit": limits[name],
                    "ok": v <= limits[name]})
    return out
