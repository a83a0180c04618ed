"""The training cell of a mixture of experts on one chip's share of its
experts (``"kind": "train_moe"``).

It is ``train_cell``'s closed loop of the trainer's jitted, donated
AdamW step, set up, timed and checked as there, with four differences:

- the rows' token ids follow a Zipf law over the vocabulary slice, in
  an id order that the seed permutes, as real text does, so routing is
  skewed;
- the check's reference is ``reference_granitemoe``;
- the configuration file states the experts this chip holds
  (``experts_held``), and the program and the reference get the same
  share;
- the step's routing counters (pairs routed to held experts, per
  expert) are reported, and the per-layer metrics read them;

and the check also fails on any reading of the program that is not
finite.

The window loop is ``train_cell.run``'s, repeated unchanged.
"""
from __future__ import annotations

import dataclasses
import gc
import sys

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import core, model, reference_granitemoe, traffic, weights
from chipbench.train_cell import _diff_norms, _keyed, _norms, compare

# program fields this cell needs: the expert share and the multipliers
_NEEDS = {"MoEConfig": ("held",),
          "ModelConfig": ("embedding_multiplier", "residual_multiplier",
                          "attention_multiplier", "logits_scaling")}
_MOE_KEYS = {"router_experts": "num_experts",
             "num_experts_per_tok": "top_k",
             "num_local_experts": "n_held",
             "intermediate_size": "d_ff",
             "router_aux_loss_coef": "aux_loss_weight"}
_MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling")


def program_config(cj: dict):
    """The program's ModelConfig for configuration file ``cj``, checked
    against the experts, the share and the multipliers it states. A
    program without an expert share fails here, before any compile."""
    from repro import models
    for cls, fields in _NEEDS.items():
        have = {f.name for f in dataclasses.fields(getattr(models, cls))}
        missing = [f for f in fields if f not in have]
        if missing:
            raise core.BenchError(f"the program's {cls} has no {missing}: "
                                  "it cannot run an expert share")
    cfg = model.program_config(cj)
    got = {key: getattr(cfg.moe, field) for key, field in _MOE_KEYS.items()}
    got.update(experts_held=list(cfg.moe.held_range))
    got.update({k: getattr(cfg, k) for k in _MULTIPLIERS})
    for key, val in got.items():
        if val != cj[key]:
            raise core.BenchError(f"{cj['name']}: program {key}={val!r}, "
                                  f"file {cj[key]!r}")
    return cfg


def zipf_rows(spec: dict, seed: int, vocab: int):
    """Endless steps of (batch, seq + 1) int32 token ids: rank r of the
    vocabulary drawn with probability proportional to r^-s, the ranks
    mapped to ids by a permutation drawn from the seed."""
    g = traffic.rng(seed, 3)
    ids = g.permutation(vocab).astype(np.int32)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -spec["zipf_exponent"]
    cdf = np.cumsum(p / p.sum())
    while True:
        u = g.random((spec["batch"], spec["seq"] + 1))
        yield ids[np.minimum(np.searchsorted(cdf, u, side="right"),
                             vocab - 1)]


def build(cell: dict, seed: int):
    """``train_cell.build`` with the expert share's config and the Zipf
    rows."""
    from repro.launch import steps as steps_lib
    from repro.optim import AdamWConfig, adamw_init
    from repro.optim.schedules import cosine_warmup
    cj, tr = cell["config"], cell["traffic"]
    tc = cj["train"]
    cfg = program_config(cj)
    opt_cfg = AdamWConfig(lr=tc["lr"], b1=tc["b1"], b2=tc["b2"],
                          eps=tc["eps"], weight_decay=tc["weight_decay"],
                          grad_clip=tc["grad_clip"])
    schedule = cosine_warmup(tc["lr"], tc["warmup"], tc["schedule_steps"])
    step = jax.jit(steps_lib.make_train_step(cfg, opt_cfg, schedule),
                   donate_argnums=(0, 1))
    params = weights.make(cfg, seed)
    opt = adamw_init(params, opt_cfg)
    feed = zipf_rows(tr, seed, cfg.vocab)

    def batch():
        rows = next(feed)
        return rows, {"tokens": jnp.asarray(rows[:, :-1]),
                      "labels": jnp.asarray(rows[:, 1:])}

    return cfg, step, params, opt, batch


def first_steps(cell: dict, seed: int) -> dict:
    """``train_cell.first_steps`` for this cell, keeping each checked
    step's routing counters as well."""
    tc = cell["config"]["train"]
    cfg, step, params, opt, batch = build(cell, seed)
    rows_seen, losses, loads, first, first_vec = [], [], [], None, None
    for s in range(cell["traffic"]["check_steps"]):
        rows, b = batch()
        rows_seen.append(rows)
        params, opt, m = step(params, opt, b, jnp.int32(s))
        losses.append(float(m["loss"]))
        loads.append(counters(m))
        if s == 0:
            first = _keyed(jax.tree_util.tree_map(
                lambda x: x / (1.0 - tc["b1"]), _norms(opt["mu"])))
            first_vec = jax.device_get(opt["mu"])
    w0 = weights.make(cfg, seed)
    change = _keyed(_diff_norms(params, w0))
    del w0
    return {"loss": losses, "grad": first, "grad_vec": first_vec,
            "change": change, "loads": loads,
            "rows": np.stack(rows_seen), "cfg": cfg, "step": step,
            "params": params, "opt": opt, "batch": batch}


def counters(m) -> dict:
    """A step's routing counters on the host: the pairs each held expert
    took and the pairs routed elsewhere, summed over layers."""
    return {"load": np.asarray(m["moe_load"]),
            "away": int(m["moe_away"])}


def pairs_per_step(loads: list[dict]) -> float:
    """Mean (token, choice) pairs a step routed to held experts."""
    return float(np.mean([c["load"].sum() for c in loads]))


def load_max_over_mean(loads: list[dict]) -> float:
    """The busiest held expert's pairs over the mean held expert's."""
    tot = np.sum([c["load"] for c in loads], axis=0)
    return float(tot.max() / max(tot.mean(), 1e-30))


def nonfinite_check(prog: dict) -> dict:
    """The program's losses and first-gradient and change norms that are
    not finite, as a check with limit 0: ``compare`` takes a NaN gap for
    no gap."""
    vals = list(prog["loss"]) + list(prog["grad"].values()) \
        + list(prog["change"].values())
    n = int(sum(not np.isfinite(v) for v in vals))
    return {"name": "nonfinite_readings", "value": n, "limit": 0,
            "ok": n == 0}


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
    loads = st.pop("loads") + [counters(m)]
    print(f"routing: pairs to held experts a step {pairs_per_step(loads)}, "
          f"away {[c['away'] for c in loads]}", file=sys.stderr)
    metrics = {"train_tok_s": rate, "setup_s": setup_s}
    layer_ctx = None
    if trace:
        layer_ctx = {"trace_dir": tdir,
                     "window_s": tctx["t1"] - tctx["t0"],
                     "train_steps": n_traced,
                     "tokens_per_step": tokens_per_step, "config": cj,
                     "traffic": tr, "moe_pairs_held": pairs_per_step(loads)}
    del params, opt, m, inflight
    gc.collect()

    # -- correctness: the reference follows the first steps ------------
    t_ref = clock()
    w = weights.make(st["cfg"], seed)
    ref = reference_granitemoe.train_steps(
        w, reference_granitemoe.dims_of(cj, model.ref_dims(cj)),
        cj["train"], st["rows"])
    print(f"reference: {clock() - t_ref:.3f} s, device peak "
          f"{core.memory_peak_bytes(jax, 1)} bytes", file=sys.stderr)
    checks = compare(st, ref, tr["limits"]) + [nonfinite_check(st)]
    return {"correct": all(c["ok"] for c in checks), "attempted": n_done,
            "failed": 0, "metrics": metrics, "memory_peak_bytes": mem,
            "layer_ctx": layer_ctx,
            "extra": {"window_compiles": win_compiles,
                      "window_steps": n_done,
                      "moe_pairs_held": pairs_per_step(loads),
                      "moe_load_max_over_mean": load_max_over_mean(loads)}
            }, checks
