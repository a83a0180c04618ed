"""The check that decides ``correct`` fails when the timed path is
broken, and its control (the reference in float8 in the program's
place) fails it too.

Each run test drives a whole run at toy sizes on the CPU, with the
harness's look for a chip skipped, and the program broken underneath:
a step that returns its state unchanged, or half of the batch left out
of the loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import core

DATA = core.HERE / "tests" / "data"


def _run(workload):
    from chipbench import run
    return run.main(["--workload", workload, "--seed", "2718281828459",
                     "--seconds", "1", "--trace", "0"], require_tpu=False,
                    manifest=DATA / "BENCHMARK.json",
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
def test_training_run_is_correct_only_when_sound(fault, monkeypatch):
    from repro.launch import steps
    from repro.models import lm
    if fault == "state_unchanged":
        monkeypatch.setattr(steps, "adamw_update", _no_update)
    elif fault == "half_batch":
        monkeypatch.setattr(lm, "loss_fn", _half_batch(lm.loss_fn))
    res = _run("tiny-finetune")
    assert res["correct"] is (fault == "none")
    if fault == "half_batch":
        gap = res["checks"]["grad_dir_gap"]
        assert gap["value"] > gap["limit"]


def _toy_cell():
    man = core.load_json(DATA / "BENCHMARK.json")
    return core.load_cell(man, "tiny-finetune", core.ROOT,
                          DATA / "traffic")


def test_training_control_fails_the_check():
    from chipbench import model, reference, train_cell, weights
    cell = _toy_cell()
    cj, tr = cell["config"], cell["traffic"]
    prog = train_cell.first_steps(cell, 7)
    w = weights.make(prog["cfg"], 7)
    dims = model.ref_dims(cj)
    ref = reference.train_steps(w, dims, cj["train"], prog["rows"])
    low = reference.train_steps(w, dims, cj["train"], prog["rows"],
                                quant="fp8")
    sound = train_cell.compare(prog, ref, tr["limits"])
    control = train_cell.compare(low, ref, tr["limits"])
    assert all(c["ok"] for c in sound)
    assert not all(c["ok"] for c in control)


def test_fp8_control_backward_runs_in_fp8():
    """The control's gradient is the float32 one up to fp8 rounding, also
    where the cotangents are tiny (a loss scaled by 1 / tokens): none is
    flushed to zero on its way through the rounding."""
    from chipbench import reference
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 1e-3
    b = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    c = jax.random.normal(jax.random.PRNGKey(2), (64, 16))

    def loss(quant, a, b):
        return 1e-7 * jnp.sum(c * reference._mm(quant)("ij,jk->ik", a, b))

    ga, gb = jax.grad(lambda a, b: loss(None, a, b), (0, 1))(a, b)
    qa, qb = jax.grad(lambda a, b: loss("fp8", a, b), (0, 1))(a, b)
    for g, q in ((ga, qa), (gb, qb)):
        rel = float(jnp.linalg.norm(q - g) / jnp.linalg.norm(g))
        assert 1e-3 < rel < 0.2


@pytest.mark.parametrize("flip,expect", [(1.0, 0.0), (-1.0, 2.0)])
def test_direction_gap_is_one_minus_cosine(flip, expect):
    from chipbench import train_cell
    rng = np.random.default_rng(0)
    ref = {"a": rng.normal(size=(8, 4)).astype(np.float32),
           "b": rng.normal(size=(5,)).astype(np.float32)}
    prog = {"a": ref["a"] * 3.0, "b": flip * ref["b"]}
    gap, where = train_cell.direction_gap(prog, ref, {"['a']", "['b']"})
    assert gap == pytest.approx(expect, abs=1e-6)
    if flip < 0:
        assert where == "['b']"
    orth = {"a": ref["a"], "b": np.zeros(5, np.float32)}
    assert train_cell.direction_gap(orth, ref, {"['b']"})[0] == \
        pytest.approx(1.0)
    assert train_cell.direction_gap(prog, ref, set())[0] == 0.0
