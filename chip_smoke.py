"""Smoke test of this repository's main path on a TPU.

One process (a chip belongs to one process at a time), in phases; each
prints its own lines and a verdict:

  a. device   JAX sees a TPU. There is no CPU fallback.
  b. kernels  the Pallas kernels, compiled (``interpret=False``) at
              smollm-135m widths, against their ``kernels/ref.py`` oracles;
              the training mix's pair (forward and gradients) against
              the XLA blockwise path, which is printed beside it.
  c. serve    smollm-135m at full width with ``--use-kernel`` through
              ``repro.launch.serve.main``: 8 requests, prompts of 64-512
              tokens, chunked prefill, 32 generated tokens each. Both
              serve paths are the fused kernels, compiled into the step
              programs (``tpu_custom_call``), and the first two tokens'
              logits match the jnp path (``use_kernel=False``).
  d. train    a few steps of ``repro.launch.train.main`` at full width:
              the loss is finite and falls.

``--chips 4`` runs phase a and then only what exists across chips: the
serving engine on a ``data=4`` mesh against the one-device engine (the
token streams agree, or part only at near-ties of the logits), and two
train steps on ``data=4`` whose losses match the one-device steps.

Weights are random, made from ``--seed 0``. Usage, from the repository
root on a machine with a TPU:

    python chip_smoke.py             # one chip: phases a-d
    python chip_smoke.py --chips 4   # four chips: phase a + mesh checks

The last line of stdout is ``{"ok": true, "device": {...}}``. A failed
phase makes the exit code 1 and suppresses that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs as cfgs  # noqa: E402
from repro.core import linear_attention as la  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.linear_attn_scan import (  # noqa: E402
    linear_attention_causal_carry_fwd)
from repro.kernels.prf_decode_step import prf_decode_step_fwd  # noqa: E402
from repro.kernels.prf_featmap import prf_featmap_fwd  # noqa: E402
from repro.kernels.prf_fused_decode import prf_fused_decode_fwd  # noqa: E402
from repro.kernels.prf_fused_prefill import (  # noqa: E402
    prf_fused_prefill_fwd)
from repro.kernels.wkv6_scan import wkv6_fwd  # noqa: E402
from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402

ARCH = "smollm-135m"
# prompt tokens per internal chunk of the fused prefill kernel, as the
# model calls it (rf_attention_prefill's default). Both the kernel and
# the jnp path take one stabilizer max per call of at most this length;
# across a longer call they would take different ones.
CHUNK = 256

# Phase b: max |kernel - oracle| over max |oracle|, per output. The
# oracles run in f32 at the highest matmul precision; the kernels' f32
# matmuls take one bf16 MXU pass (Mosaic's default, as served). The
# inputs are bf16-representable, as the model's activations are, so that
# pass rounds no input: at the feature projection an input's rounding
# (2^-8) would pass through the exp of the PRF features amplified (with
# f32 inputs the fused prefill state was off by 3.4e-2 on a v5e). What
# is left is the rounding of intermediates (features, states) at 2^-8 =
# 3.9e-3 each. A wrong block, index or mask is off by the size of the
# output itself.
KERNEL_TOL = 2e-2

# Phase c: max |logits(fused kernels) - logits(jnp path)| over
# max |logits(jnp path)|. The model runs in bfloat16 (2^-8 relative per
# rounding) and the two paths round at different points in every one of
# the 30 layers; an indexing or masking fault in a kernel moves the
# logits by their own size.
LOGIT_TOL = 5e-2

# --chips 4: |loss(data=4) - loss(1 device)| over loss(1 device). Data
# parallelism changes only the order of the batch reductions (the loss
# mean, the gradient all-reduce).
MESH_LOSS_TOL = 1e-3

# --chips 4, serving: a data=4 engine runs each chip's share of the
# slots through programs compiled for a smaller batch than the
# one-device engine's. In bfloat16 these round differently, the gap
# grows with every decoded token, and greedy streams of random weights
# (logits spanning ~0.5 over 49152 tokens, the top two typically ~5% of
# max |logits| apart) part at near-ties: on a v5e 7 of 8 streams parted
# within 32 tokens, at margins up to 1.2e-1. So both engines serve in
# float32 at the highest matmul precision, where the programs differ by
# the order of their reductions only, and the streams should be
# identical. A stream may still take the other side of an exact tie:
# where the two engines' streams first differ, the one-device model's
# logits of the two tokens, recomputed by a prefill of the context in
# CHUNK-token calls (not the engines' schedule), must lie within this
# share of max |logits|. A wrong slot, shard or state picks a token from
# the bulk of the distribution, a margin near 1 (0.5 for a token forced
# off by one on the CPU).
MESH_TIE_TOL = 5e-2

SERVE_ARGS = ["--arch", ARCH, "--use-kernel", "--requests", "8",
              "--slots", "8", "--prompt-len", "64-512", "--gen", "32",
              "--chunk-tokens", "256", "--max-len", "576", "--seed", "0"]
TRAIN_ARGS = ["--arch", ARCH, "--steps", "8", "--batch", "8", "--seq",
              "512", "--lr", "1e-3", "--warmup", "2", "--log-every", "1",
              "--seed", "0"]
MESH_SERVE_ARGS = [a for a in SERVE_ARGS if a != "--use-kernel"] + [
    "--dtype", "float32"]
MESH_TRAIN_ARGS = ["--arch", ARCH, "--steps", "2", "--batch", "8",
                   "--seq", "512", "--lr", "1e-3", "--warmup", "1",
                   "--log-every", "1", "--seed", "0"]


def check(ok, what) -> None:
    """Fail the phase unless ``ok`` (an assert would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


def _gap(got, want) -> float:
    """max |got - want| over max |want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> None:
    devs = jax.devices()
    d = devs[0]
    print(f"  platform={d.platform} kind={d.device_kind} count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: JAX runs on {d.platform}")
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")


# ---------------------------------------------------------------------------
# b. kernels
# ---------------------------------------------------------------------------

# the training cell's floor under the PRF attention denominator
MIX_EPS = 1e-30


def ops_mix(qf, kf, v):
    """The training step's causal PRF mix as it runs on the chip: the
    Pallas pair (one row per KV group, a backward of its own)."""
    return ops.linear_attention_causal(qf, kf, v, eps=MIX_EPS)


def _blockwise(qf, kf, v):
    """The XLA path the pair replaced (and its oracle)."""
    return la.linear_attention_causal_blockwise(
        qf, jnp.broadcast_to(kf, qf.shape),
        jnp.broadcast_to(v, qf.shape[:-1] + v.shape[-1:]), eps=MIX_EPS)


def _train_mix(mix):
    """(out, dqf, dkf, dv) of ``mix`` for an output cotangent ``g``."""
    def run(qf, kf, v, g, interpret=False):
        out, vjp = jax.vjp(mix, qf, kf, v)
        return (out,) + vjp(g)
    return run


def _mix_oracle(qf, kf, v, g):
    return _train_mix(_blockwise)(qf, kf, v, g)


def _kernel_cases(seed: int = 0):
    """(name, kernel, oracle, arguments) at smollm-135m attention
    widths."""
    cfg = cfgs.get_config(ARCH)
    g, d, m = cfg.n_kv, cfg.head_dim, cfg.attn.num_features
    hg = cfg.n_heads // g
    slots, rows, l = 8, 8, 512
    n = rows * cfg.n_heads
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 48))

    def normal(shape, scale=1.0):
        return scale * jax.random.normal(next(ks), shape)

    def positive(shape):
        return jnp.exp(0.5 * normal(shape)) * m ** -0.5

    # data-aligned feature map as the model holds it: scaled raw q/k,
    # M near identity, A = (W M)^T precomposed
    m_mat = jnp.eye(d) + normal((g, d, d), 0.1)
    w = normal((g, m, d))
    a = jnp.einsum("gmr,grd->gdm", w, m_mat)
    qs = d ** -0.25

    dec = (normal((slots, g, hg, d), qs), normal((slots, g, d), qs),
           normal((slots, g, d)), a, m_mat, normal((slots, g, hg, m, d)),
           jax.random.uniform(next(ks), (slots, g, hg, m)) + 0.5,
           normal((slots, g)))
    vl = jnp.asarray([512, 300, 64, 1, 256, 257, 511, 128], jnp.int32)
    pre = (normal((rows, g, hg, l, d), qs), normal((rows, g, l, d), qs),
           normal((rows, g, l, d)), a, m_mat, normal((rows, g, hg, m, d)),
           jax.random.uniform(next(ks), (rows, g, hg, m)) + 0.5,
           normal((rows, g)), vl)
    lin = (positive((n, l, m)), positive((n, l, m)), normal((n, l, d)))
    mix = (positive((rows, g, hg, l, m)), positive((rows, g, 1, l, m)),
           normal((rows, g, 1, l, d)), normal((rows, g, hg, l, d)))
    carry = lin + (normal((n, m, d)),
                   jax.random.uniform(next(ks), (n, m)) + 0.5)
    feat = (normal((rows * l, d), qs), m_mat[0], w[0], jnp.float32(0.0))
    step = (positive((n, m)), positive((n, m)), normal((n, d)),
            normal((n, m, d)),
            jax.random.uniform(next(ks), (n, m)) + 0.5,
            jax.random.uniform(next(ks), (n, 1), minval=0.5, maxval=1.0))
    dh = 64                                  # every RWKV-6 head
    wkv = (normal((16, l, dh), 0.5), normal((16, l, dh), 0.5),
           normal((16, l, dh), 0.5),
           jnp.exp(-jnp.exp(normal((16, l, dh), 0.5) - 1.0)),
           normal((dh,), 0.5))

    def prefill_ref(q, k, v, a_, m_, s, z, c, vl_):
        # the kernel advances its stabilizer once per CHUNK-token grid
        # step: the oracle resumed chunk by chunk is its ground truth
        outs = []
        for i in range(0, l, CHUNK):
            o, s, z, c = ref.prf_fused_prefill_ref(
                q[:, :, :, i:i + CHUNK], k[:, :, i:i + CHUNK],
                v[:, :, i:i + CHUNK], a_, m_, s, z, c,
                jnp.clip(vl_ - i, 0, CHUNK))
            outs.append(o)
        return jnp.concatenate(outs, axis=3), s, z, c

    def wkv_ref(r, k, v, w_, u):
        return ref.wkv6_ref(r, k, v, w_, u,
                            jnp.zeros((r.shape[0], dh, dh)))[0]

    def bf16_exact(args):
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, args)

    dec, pre, lin, carry, feat, step, wkv, mix = map(
        bf16_exact, (dec, pre, lin, carry, feat, step, wkv, mix))
    # values and their cotangent in bf16, as the model holds them
    mix = mix[:2] + tuple(x.astype(jnp.bfloat16) for x in mix[2:])
    return [
        ("prf_fused_decode", prf_fused_decode_fwd, ref.prf_fused_decode_ref,
         dec),
        ("prf_fused_prefill", functools.partial(prf_fused_prefill_fwd,
                                                chunk=CHUNK),
         prefill_ref, pre),
        ("prf_mix (out, dqf, dkf, dv)", _train_mix(ops_mix), _mix_oracle,
         mix),
        ("  the XLA blockwise path", _train_mix(_blockwise),
         _mix_oracle, mix),
        ("linear_attention_causal_carry", linear_attention_causal_carry_fwd,
         ref.linear_attention_carry_ref, carry),
        ("prf_featmap", prf_featmap_fwd, ref.prf_featmap_ref, feat),
        ("prf_decode_step", prf_decode_step_fwd, ref.prf_decode_step_ref,
         step),
        ("wkv6", wkv6_fwd, wkv_ref, wkv),
    ]


def phase_kernels() -> None:
    worst = []
    for name, kernel, oracle, args in _kernel_cases():
        got = jax.jit(functools.partial(kernel, interpret=False))(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        got, want = jax.tree_util.tree_leaves(got), \
            jax.tree_util.tree_leaves(want)
        check(len(got) == len(want), name)
        gaps = []
        for gt, wt in zip(got, want):
            check(np.isfinite(np.asarray(gt)).all(),
                  f"{name}: non-finite output")
            gaps.append(_gap(gt, wt))
        print(f"  {name}: gap per output "
              + " ".join(f"{x:.3e}" for x in gaps))
        worst.append((max(gaps), name))
    top, name = max(worst)
    check(top <= KERNEL_TOL, f"{name}: gap {top:.3e} > {KERNEL_TOL}")


# ---------------------------------------------------------------------------
# c. serve
# ---------------------------------------------------------------------------

def _padded(prompts):
    """(tokens padded to whole CHUNKs, lengths) of a list of prompts."""
    l = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), -(-l // CHUNK) * CHUNK), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return (jnp.asarray(toks),
            jnp.asarray([len(p) for p in prompts], jnp.int32))


def _next_logits(params, cfg, toks, vl):
    """Logits of the token after each row's ``vl`` prompt tokens
    (prefill over the padded rows, CHUNK tokens per call, from fresh
    per-slot states), and the states."""
    b, l = toks.shape
    stacked = lm.can_stack_layers(cfg)

    @jax.jit
    def prefill(params, toks, vl):
        st = lm.init_serve_state(cfg, b=b, max_len=l + 1, per_slot=True,
                                 stacked=stacked)
        first = None
        for i in range(0, l, CHUNK):
            lg, st = lm.prefill_chunk(
                params, cfg, {"tokens": toks[:, i:i + CHUNK]}, st,
                valid_len=jnp.clip(vl - i, 0, CHUNK))
            # a row's first token comes from the call that holds its
            # last prompt position
            held = ((vl - 1) // CHUNK == i // CHUNK)[:, None]
            first = lg if first is None else jnp.where(held, lg, first)
        return first, st

    return prefill(params, toks, vl)


def _first_two_logits(params, cfg, toks, vl, tok=None):
    """Logits of the first generated token and of the second (one decode
    step on ``tok``, or on the greedy first token when None)."""
    first, st = _next_logits(params, cfg, toks, vl)
    if tok is None:
        tok = jnp.argmax(first, axis=-1).astype(jnp.int32)
    second, _ = jax.jit(lambda p, t, s: lm.decode_step(p, cfg, t, s))(
        params, tok, st)
    return first, second, tok


def logit_gaps(engine, prompts) -> dict[str, float]:
    """Fused-kernel vs jnp-path logits of the first two tokens of every
    prompt, with the engine's weights."""
    b = len(prompts)
    toks, vl = _padded(prompts)
    cfg_j = dataclasses.replace(engine.cfg, use_kernel=False)
    j1, j2, tok = _first_two_logits(engine.params, cfg_j, toks, vl)
    k1, k2, _ = _first_two_logits(engine.params, engine.cfg, toks, vl, tok)
    out = {}
    for name, k, j in (("first", k1, j1), ("second", k2, j2)):
        gap = _gap(k, j)
        agree = int(np.sum(np.argmax(np.asarray(k), -1)
                           == np.argmax(np.asarray(j), -1)))
        print(f"  {name}-token logits: max|kernel - jnp| = "
              f"{float(jnp.max(jnp.abs(k - j))):.4e}, max|jnp| = "
              f"{float(jnp.max(jnp.abs(j))):.4e}, gap {gap:.3e}; "
              f"argmax agrees on {agree}/{b}")
        out[name] = gap
    return out


def phase_serve(argv: list[str] = SERVE_ARGS, gen: int = 32) -> None:
    engine, results = serve.main(argv)
    st = engine.stats
    check(st["decode_path"] == st["prefill_path"] == "fused_kernel", st)
    check(all(len(r.tokens) == gen for r in results),
          [len(r.tokens) for r in results])
    gaps = logit_gaps(engine, [r.prompt for r in results])
    check(max(gaps.values()) <= LOGIT_TOL, (gaps, LOGIT_TOL))
    text = engine.compiled_text(rows=engine.max_slots, length=32)
    for name, hlo in text.items():
        n = hlo.count("tpu_custom_call")
        print(f"  compiled {name} program: {n} tpu_custom_call")
        check(n > 0, f"{name}: the Pallas kernel was not compiled in")


# ---------------------------------------------------------------------------
# d. train
# ---------------------------------------------------------------------------

def phase_train(argv: list[str] = TRAIN_ARGS) -> None:
    _, log = train.main(argv)
    losses = [m["loss"] for m in log]
    check(np.isfinite(losses).all(), losses)
    check(losses[-1] < losses[0], losses)
    print(f"  loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {len(losses)} steps")


# ---------------------------------------------------------------------------
# --chips 4
# ---------------------------------------------------------------------------

def _spread(tree) -> set[int]:
    return {len(x.sharding.device_set)
            for x in jax.tree_util.tree_leaves(tree)}


def phase_mesh_serve(n: int, argv: list[str] = MESH_SERVE_ARGS) -> None:
    with jax.default_matmul_precision("highest"):
        eng1, res1 = serve.main(argv)
        engn, resn = serve.main(argv + ["--mesh-data", str(n)])
        spread = _spread(engn.pool) | _spread(engn.params)
        print(f"  data={n} engine: pool and params on {spread} devices")
        check(spread == {n}, spread)
        check_streams(eng1, res1, resn, n)


def check_streams(eng1, res1, resn, n: int) -> None:
    """The one-device and data=n engines' greedy streams agree, or part
    only at a near-tie of the one-device model's logits (MESH_TIE_TOL)."""
    res1 = sorted(res1, key=lambda r: r.uid)
    resn = sorted(resn, key=lambda r: r.uid)
    split = []                  # (context, one-device token, data=n token)
    for a, b in zip(res1, resn):
        check(list(a.prompt) == list(b.prompt), "different prompts")
        at = next((i for i, (x, y) in enumerate(zip(a.tokens, b.tokens))
                   if x != y), None)
        if at is not None:
            split.append((list(a.prompt) + a.tokens[:at], a.tokens[at],
                          b.tokens[at], at))
    print(f"  token streams identical for {len(res1) - len(split)}/"
          f"{len(res1)} requests")
    if not split:
        return
    logits, _ = _next_logits(eng1.params, eng1.cfg,
                             *_padded([c for c, *_ in split]))
    worst = 0.0
    for row, (_, t1, tn, at) in zip(np.asarray(logits, np.float32), split):
        margin = float(row[t1] - row[tn]) / float(np.max(np.abs(row)))
        print(f"  streams part at generated token {at}: 1 device {t1}, "
              f"data={n} {tn}; logit margin {margin:.3e} of max|logits|")
        worst = max(worst, abs(margin))
    check(worst <= MESH_TIE_TOL,
          f"not a near-tie: {worst:.3e} > {MESH_TIE_TOL}")


def phase_mesh_train(n: int, argv: list[str] = MESH_TRAIN_ARGS) -> None:
    _, log1 = train.main(argv)
    state, logn = train.main(argv + ["--mesh-data", str(n)])
    spread = _spread(state)
    print(f"  data={n} train state on {spread} devices")
    check(spread == {n}, spread)
    for a, b in zip(log1, logn):
        rel = abs(b["loss"] - a["loss"]) / abs(a["loss"])
        print(f"  step {a['step']}: loss 1 device {a['loss']:.6f}, "
              f"data={n} {b['loss']:.6f}, relative gap {rel:.3e}")
        check(rel <= MESH_LOSS_TOL, (a, b))


# ---------------------------------------------------------------------------

def _run(name: str, fn) -> bool:
    """Run one phase; report, never hide, its failure."""
    print(f"[{name}]", flush=True)
    t0 = time.perf_counter()
    try:
        fn()
    except Exception:  # a phase boundary: report it, then go on
        traceback.print_exc()
        print(f"[{name}] FAIL ({time.perf_counter() - t0:.1f}s)",
              flush=True)
        return False
    print(f"[{name}] PASS ({time.perf_counter() - t0:.1f}s)", flush=True)
    return True


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip checks")
    args = ap.parse_args(argv)
    if not _run("a. device", lambda: phase_device(args.chips)):
        return 1
    setup_compile_cache()
    if args.chips == 1:
        phases = [("b. kernels", phase_kernels), ("c. serve", phase_serve),
                  ("d. train", phase_train)]
    else:
        phases = [("mesh serve", lambda: phase_mesh_serve(args.chips)),
                  ("mesh train", lambda: phase_mesh_train(args.chips))]
    results = [_run(name, fn) for name, fn in phases]
    if not all(results):
        return 1
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
