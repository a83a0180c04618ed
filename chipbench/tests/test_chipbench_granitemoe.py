"""The ``train_moe`` kind: the Zipf rows, the expert share's config
check, the cost functions against hand counts, the expert metrics'
readers, the program against ``reference_granitemoe`` at a small size,
and whole toy runs that come out correct only when the program is
sound."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import core

DATA = core.HERE / "tests" / "data"


def _toy_cell():
    man = core.load_json(DATA / "BENCHMARK.moe.json")
    return core.load_cell(man, "tiny-finetune-moe", core.ROOT,
                          DATA / "traffic")


def _granite():
    return core.load_json(core.HERE / "configs"
                          / "granite-moe-3b-a800m-train.json")


def _run():
    from chipbench import run
    return run.main(["--workload", "tiny-finetune-moe", "--seed",
                     "2718281828459", "--seconds", "1", "--trace", "0"],
                    require_tpu=False, manifest=DATA / "BENCHMARK.moe.json",
                    traffic_dir=DATA / "traffic")


def _no_update(params, grads, state, cfg, lr):
    return params, state, {"grad_norm": jnp.zeros(()),
                           "lr": jnp.asarray(lr, jnp.float32)}


def _half_batch(orig):
    def loss_fn(params, cfg, batch, rng=None):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return orig(params, cfg, half, rng)
    return loss_fn


@pytest.mark.parametrize("fault", ["none", "state_unchanged",
                                   "half_batch"])
def test_moe_run_is_correct_only_when_sound(fault, monkeypatch):
    """A sound program passes the check; one whose step leaves the state
    unchanged, or whose loss leaves out half of the batch, fails it."""
    from repro.launch import steps
    from repro.models import lm
    if fault == "state_unchanged":
        monkeypatch.setattr(steps, "adamw_update", _no_update)
    elif fault == "half_batch":
        monkeypatch.setattr(lm, "loss_fn", _half_batch(lm.loss_fn))
    res = _run()
    assert res["correct"] is (fault == "none")
    # every pair of the toy's 2 layers x 2 rows x 32 tokens x top 2 is
    # counted, on the 4 experts held or away
    assert 0 < res["moe_pairs_held"] < 2 * 2 * 32 * 2
    assert res["moe_load_max_over_mean"] >= 1.0
    if fault == "half_batch":
        gap = res["checks"]["grad_dir_gap"]
        assert gap["value"] > gap["limit"]


def test_zipf_rows_follow_the_seed():
    from chipbench import train_moe_cell
    spec = {"batch": 2, "seq": 4095, "zipf_exponent": 1.0}
    a, b = (next(train_moe_cell.zipf_rows(spec, 2147483648 + 5, 24576))
            for _ in range(2))
    c = next(train_moe_cell.zipf_rows(spec, 2147483648 + 6, 24576))
    assert a.shape == (2, 4096) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert 0 <= a.min() and a.max() < 24576
    # Zipf with exponent 1 over 24576 ranks: the first rank takes
    # 1 / H(24576) ~ 9.3% of the draws, uniform ids 0.004%
    ids, counts = np.unique(a, return_counts=True)
    assert 0.07 < counts.max() / a.size < 0.12
    # and which id is the commonest depends on the seed
    top_c = np.bincount(c.ravel(), minlength=24576).argmax()
    assert ids[counts.argmax()] != top_c


def test_the_share_is_checked_before_any_compile(monkeypatch):
    """The configuration's share must be the program's, and a program
    with no expert share fails at once with a BenchError."""
    from repro import models
    from chipbench import train_moe_cell
    cj = _granite()
    cfg = train_moe_cell.program_config(cj)
    assert cfg.moe.held_range == (0, 20) and cfg.moe.num_experts == 40
    assert cfg.vocab == 24576 and cfg.n_layers == 8
    with pytest.raises(core.BenchError):
        train_moe_cell.program_config(dict(cj, experts_held=[20, 40]))
    with pytest.raises(core.BenchError):
        train_moe_cell.program_config(dict(cj, residual_multiplier=1.0))

    @dataclasses.dataclass(frozen=True)
    class OldMoE:
        num_experts: int
        top_k: int
        d_ff: int
        capacity_factor: float = 1.25

    monkeypatch.setattr(models, "MoEConfig", OldMoE)
    with pytest.raises(core.BenchError, match="expert share"):
        train_moe_cell.program_config(cj)


def test_runner_is_found_by_kind():
    assert core.cell_runner(_toy_cell()).__name__ == \
        "chipbench.train_moe_cell"


def _costs():
    return core.load_module("costs", "granitemoe")


def test_gmm_costs_hand_count():
    costs = _costs()
    cj = _granite()
    pairs = 1000
    # per pair: gate and up 2 x 1536 x 512 each, out 2 x 512 x 1536,
    # and the backward twice that
    assert costs.gmm_flops(cj, pairs) == 3 * 3 * 2 * 1536 * 512 * pairs
    # nine matmuls, each reading and writing every pair's row once
    # (1536 or 512 wide) and the held weights (20 x 1536 x 512 each,
    # in each of 8 layers), 2 bytes an element
    acts = pairs * (3 * 1536 + 3 * 512 + 6 * 1536 + 6 * 512)
    weights = 8 * 9 * 20 * 1536 * 512
    assert costs.gmm_bytes(cj, pairs) == 2 * (acts + weights)


def test_moe_train_flops_hand_count():
    costs = _costs()
    model = core.load_module("costs", "model")
    cj = _granite()
    attn = 1536 * (24 + 16) * 64 + 24 * 64 * 1536
    dense = 8 * (attn + 1536 * 40) + 1536 * 24576
    assert costs.dense_params_per_token(cj) == dense
    assert abs(dense - 88.57e6) < 0.01e6
    tokens, pairs = 8192, 8192 * 8 * 8 / 2
    want = 6 * (tokens * dense + pairs * 3 * 1536 * 512) \
        + 3 * tokens * 8 * model.attention_flops_per_token(cj)
    assert costs.train_flops(cj, tokens, pairs) == want
    # ~1.04 GFLOP a token at half of the pairs held
    assert 1.0e9 < want / tokens < 1.1e9


def test_moe_scope_is_the_innermost_expert_part():
    from chipbench import moe_scopes, scopes
    path = ("jit(train_step)/transpose(jvp(while))/body/checkpoint/mlp/"
            "transpose(jvp(moe_experts))/ragged_dot_general:")
    assert moe_scopes.moe_scope_of(path) == "moe_experts"
    assert scopes.scope_of(path) == "mlp"
    assert moe_scopes.moe_scope_of(
        "jit(train_step)/mlp/moe_dispatch/gather") == "moe_dispatch"
    assert moe_scopes.moe_scope_of("jit(train_step)/mlp/dot_general") \
        is None


def test_grouped_matmuls_are_read_by_their_op_name(monkeypatch):
    """XLA's ragged-dot custom calls carry no scope (their ``tf_op`` is
    their own name, as in a v5e trace): they count as the experts'."""
    from chipbench import moe_scopes, scopes, trace
    Op = scopes.Op
    ops = [Op("%ragged-dot-none.3 = bf16[65536,512]{1,0} custom-call(%a)",
              0, 3e6, "ragged-dot-none:"),
           Op("%fusion.9 = bf16[65536,1536]{1,0} fusion(%b)", 3e6, 4e6,
              "jit(train_step)/jvp()/while/body/closed_call/mlp/"
              "moe_dispatch/gather:"),
           Op("%fusion.7 = f32[8]{0} fusion(%c)", 4e6, 9e6,
              "jit(train_step)/optimizer/mul:")]
    monkeypatch.setattr(trace, "xplane_file", lambda d: d)
    monkeypatch.setattr(scopes, "step_ops", lambda f, lo, hi: (ops, 1))
    ctx = {"trace_dir": "t", "span_ns": (0, 1e7)}
    assert moe_scopes.moe_ms(ctx, ("moe_experts",)) == pytest.approx(3.0)
    assert moe_scopes.moe_ms(ctx, ("moe_dispatch",)) == pytest.approx(1.0)
    assert scopes.scope_of(ops[0].tf_op) == "unscoped"


def _ctx(**kw):
    ns = {"moe_router": 2e6, "moe_dispatch": 4e6, "moe_experts": 60e6,
          "moe_combine": 6e6}                    # over 2 executions
    peaks = core.peaks("TPU v5 lite")
    return dict({"moe_scopes": (ns, 2), "config": _granite(),
                 "peaks": peaks, "tokens_per_step": 8192,
                 "costs": lambda name: core.load_module("costs", name)},
                **kw)


def test_moe_metric_readers_on_known_times():
    route = core.load_module("metrics", "moe_route_ms.train")
    expert = core.load_module("metrics", "moe_expert_ms.train")
    roof = core.load_module("metrics", "moe_gmm_roofline.train")
    mfu = core.load_module("metrics", "moe_train_mfu")
    ctx = _ctx(moe_pairs_held=262144.0, train_steps=10, window_s=4.0)
    assert route.read(ctx) == pytest.approx(6.0)
    assert expert.read(ctx) == pytest.approx(30.0)
    costs = _costs()
    flops_s = costs.gmm_flops(ctx["config"], 262144) / 197e12
    assert roof.read(ctx) == pytest.approx(100 * flops_s / 0.030)
    step = costs.train_flops(ctx["config"], 8192, 262144)
    assert mfu.read(ctx) == pytest.approx(100 * 10 * step / (4 * 197e12))
    # a run that counted no pairs (a program without them) reads nothing
    bare = _ctx(train_steps=10, window_s=4.0)
    assert roof.read(bare) is None and mfu.read(bare) is None
    assert route.read(_ctx(moe_scopes=({}, 2))) is None


def test_program_matches_the_reference():
    """``lm.forward_train`` with Granite's multipliers and an expert
    share equals ``reference_granitemoe.logits`` row by row, in float32
    at a small size, and the program's routing counters account for
    every (token, choice) pair, held here or away."""
    from chipbench import model, reference_granitemoe as ref, weights
    from chipbench.train_moe_cell import program_config
    from repro.models import lm
    cj = _toy_cell()["config"]
    cfg = program_config(cj)
    assert cfg.residual_multiplier == 0.22 and cfg.moe.held == (2, 6)
    w = weights.make(cfg, 11)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0, cfg.vocab)
    got, aux = lm.forward_train(w, cfg, {"tokens": toks})
    dims = ref.dims_of(cj, model.ref_dims(cj))
    share = jnp.zeros((cfg.n_layers, cfg.moe.num_experts))
    w32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), w)
    with jax.default_matmul_precision("highest"):
        for r in range(2):
            want, _, _ = ref.logits(w32, dims, toks[r], share)
            np.testing.assert_allclose(np.asarray(got[r]),
                                       np.asarray(want), atol=2e-4,
                                       rtol=2e-4)
    n = 2 * 24 * cfg.moe.top_k * cfg.n_layers
    assert int(jnp.sum(aux["moe_load"])) + int(aux["moe_away"]) == n
    assert int(aux["moe_away"]) > 0
