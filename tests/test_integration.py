"""End-to-end launcher tests (subprocess, CPU, reduced configs)."""
import json
import os
import subprocess
import sys

import pytest

# multi-device subprocesses / full launcher runs: minutes of
# wall-clock; skipped by scripts/check.sh --fast
pytestmark = pytest.mark.slow


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cmd(args, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-m"] + args,
                         capture_output=True, text=True, env=env,
                         timeout=timeout, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-3000:] + "\n" + out.stderr[-3000:]
    return out.stdout


def test_train_launcher_loss_decreases(tmp_path):
    mfile = tmp_path / "metrics.json"
    run_cmd(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
             "--steps", "60", "--batch", "8", "--seq", "64",
             "--lr", "3e-3", "--log-every", "5",
             "--metrics-out", str(mfile)])
    metrics = json.load(open(mfile))
    first, last = metrics[0], metrics[-1]
    assert last["loss"] < first["loss"] - 0.1, (first, last)
    assert all(m["loss"] == m["loss"] for m in metrics)     # no NaN


def test_train_checkpoint_restart_failure_injection(tmp_path):
    """Injected failure mid-run: final metrics equal the clean run."""
    clean = tmp_path / "clean"
    faulty = tmp_path / "faulty"
    m1 = tmp_path / "m1.json"
    m2 = tmp_path / "m2.json"
    common = ["repro.launch.train", "--arch", "smollm-135m", "--reduced",
              "--steps", "30", "--batch", "4", "--seq", "32",
              "--ckpt-every", "10", "--log-every", "29"]
    run_cmd(common + ["--ckpt-dir", str(clean), "--metrics-out", str(m1)])
    run_cmd(common + ["--ckpt-dir", str(faulty), "--metrics-out", str(m2),
                      "--simulate-failure-at", "15"])
    a = json.load(open(m1))[-1]
    b = json.load(open(m2))[-1]
    assert abs(a["loss"] - b["loss"]) < 1e-4, (a, b)


def test_finetune_from_checkpoint_and_qkv_only(tmp_path):
    ck = tmp_path / "pretrain"
    run_cmd(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
             "--kernel", "exact", "--steps", "12", "--batch", "4",
             "--seq", "32", "--ckpt-dir", str(ck), "--ckpt-every", "6"])
    # finetune with the PRF kernel from the exact-attention checkpoint is
    # exercised at the API level in test_finetune_api (param trees differ);
    # here: resume same kernel with qkv-only freezing.
    m = tmp_path / "m.json"
    run_cmd(["repro.launch.train", "--arch", "smollm-135m", "--reduced",
             "--kernel", "exact", "--steps", "6", "--batch", "4",
             "--seq", "32", "--finetune-from", str(ck), "--qkv-only",
             "--metrics-out", str(m)])
    assert json.load(open(m))


def test_serve_launcher_decodes():
    """The serve CLI drives the continuous-batching engine: more requests
    than slots, heterogeneous lengths, full stats report."""
    out = run_cmd(["repro.launch.serve", "--arch", "smollm-135m",
                   "--reduced", "--requests", "3", "--slots", "2",
                   "--prompt-len", "8-16", "--gen", "8",
                   "--max-len", "48", "--dtype", "bfloat16"])
    assert "throughput:" in out and "slot occupancy:" in out
    assert "dtype=bfloat16" in out
    assert out.count("req ") == 3


def test_serve_launcher_hybrid():
    out = run_cmd(["repro.launch.serve", "--arch", "recurrentgemma-2b",
                   "--reduced", "--requests", "2", "--slots", "2",
                   "--prompt-len", "12", "--gen", "6",
                   "--max-len", "32", "--kernel", "darkformer"])
    assert "throughput:" in out


def test_qkv_only_freeze_semantics():
    """qkv-only training changes ONLY wq/wk/wv/m_mat leaves."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import configs as cfgs
    from repro.launch import steps as steps_lib
    from repro.models import lm
    from repro.optim import AdamWConfig, adamw_init
    from repro.optim.schedules import constant
    from repro.data import SyntheticLM

    cfg = cfgs.get_config("smollm-135m", reduced=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt_cfg = AdamWConfig(lr=1e-2, weight_decay=0.0)
    opt = adamw_init(params, opt_cfg)
    step = steps_lib.make_train_step(cfg, opt_cfg, constant(1e-2),
                                     freeze=steps_lib.qkv_only_freeze)
    batch = dict(SyntheticLM(cfg.vocab, 32, 4).batch(0))
    p2, _, _ = jax.jit(step)(params, opt, batch, jnp.int32(0))
    flat1 = jax.tree_util.tree_flatten_with_path(params)[0]
    flat2 = jax.tree_util.tree_flatten_with_path(p2)[0]
    for (path, a), (_, b) in zip(flat1, flat2):
        ps = jax.tree_util.keystr(path)
        changed = bool(jnp.any(a != b))
        trainable = any(k in ps for k in ("['wq']", "['wk']", "['wv']",
                                          "['m_mat']"))
        assert changed == trainable, (ps, changed, trainable)


def test_finetune_api_exact_to_darkformer():
    """The paper's main scenario: pretrained exact-attention weights are
    reused under the darkformer kernel (config change + feat params init),
    and finetuning improves loss."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import configs as cfgs
    from repro.launch import steps as steps_lib
    from repro.models import lm
    from repro.optim import AdamWConfig, adamw_init
    from repro.optim.schedules import constant
    from repro.data import SyntheticLM

    cfg_e = cfgs.darkify(cfgs.get_config("smollm-135m", reduced=True),
                         "exact")
    p_exact = lm.init_params(jax.random.PRNGKey(0), cfg_e)
    cfg_d = cfgs.darkify(cfg_e, "darkformer", 32)
    p_dark = lm.init_params(jax.random.PRNGKey(0), cfg_d)
    # transplant every shared leaf (checkpoint surgery)
    flat_e = {jax.tree_util.keystr(k): v for k, v in
              jax.tree_util.tree_flatten_with_path(p_exact)[0]}
    flat_d, tdef = jax.tree_util.tree_flatten_with_path(p_dark)
    merged = [flat_e.get(jax.tree_util.keystr(k), v) for k, v in flat_d]
    p_dark = jax.tree_util.tree_unflatten(tdef, merged)
    data = SyntheticLM(cfg_d.vocab, 32, 8)
    opt_cfg = AdamWConfig(lr=3e-3)
    opt = adamw_init(p_dark, opt_cfg)
    step = jax.jit(steps_lib.make_train_step(cfg_d, opt_cfg,
                                             constant(3e-3)))
    losses = []
    for i in range(25):
        p_dark, opt, m = step(p_dark, opt, dict(data.batch(i)),
                              jnp.int32(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
