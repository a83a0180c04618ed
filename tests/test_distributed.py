"""Multi-device tests: run in subprocesses with 8 fake host devices so the
main test process keeps seeing 1 device (per the dry-run contract)."""
import os
import subprocess
import sys
import textwrap

import pytest

# multi-device subprocesses / full launcher runs: minutes of
# wall-clock; skipped by scripts/check.sh --fast
pytestmark = pytest.mark.slow


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_grad_compression_shard_map():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_local_mesh
        from repro.parallel import compressed_psum_mean, init_error_feedback

        mesh = make_local_mesh(8, 1)
        g_local = jnp.stack([jnp.full((4,), float(i)) for i in range(8)])
        expect = np.full((4,), np.mean(range(8)), np.float32)

        def body_none(g):
            out, _ = compressed_psum_mean({"g": g[0]}, ("data",), "none")
            return out["g"][None]
        out = shard_map(body_none, mesh=mesh, in_specs=P("data", None),
                        out_specs=P("data", None))(g_local)
        np.testing.assert_allclose(np.asarray(out[0]), expect, rtol=1e-6)

        def body_bf16(g):
            out, _ = compressed_psum_mean({"g": g[0]}, ("data",), "bf16")
            return out["g"][None]
        out = shard_map(body_bf16, mesh=mesh, in_specs=P("data", None),
                        out_specs=P("data", None))(g_local)
        np.testing.assert_allclose(np.asarray(out[0]), expect, rtol=2e-2)

        eb = init_error_feedback({"g": g_local[0]})
        def body_int8(g, e):
            out, eb2 = compressed_psum_mean({"g": g[0]}, ("data",), "int8",
                                            {"g": e[0]})
            return out["g"][None], eb2["g"][None]
        out, eb2 = shard_map(body_int8, mesh=mesh,
                             in_specs=(P("data", None), P("data", None)),
                             out_specs=(P("data", None), P("data", None)))(
            g_local, jnp.broadcast_to(eb["g"], (8, 4)))
        np.testing.assert_allclose(np.asarray(out[0]), expect, rtol=0.05)
        print("COMPRESSION_OK")
    """))


def test_int8_error_feedback_converges():
    """Error feedback makes the *average over steps* unbiased: constant
    gradient reduced with int8+EF accumulates to the exact sum."""
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_local_mesh
        from repro.parallel import compressed_psum_mean

        mesh = make_local_mesh(8, 1)
        g_const = jnp.linspace(-1.0, 1.0, 4)

        def step(e):
            out, eb = compressed_psum_mean(
                {"g": g_const}, ("data",), "int8", {"g": e})
            return out["g"], eb["g"]

        def run(e0):
            tot = jnp.zeros(4)
            e = e0
            for _ in range(64):
                o, e = step(e)
                tot = tot + o
            return tot[None]

        tot = shard_map(run, mesh=mesh, in_specs=P("data", None),
                        out_specs=P("data", None))(jnp.zeros((8, 4)))
        np.testing.assert_allclose(np.asarray(tot[0, 0] / 64),
                                   np.asarray(g_const), atol=1e-3)
        print("EF_OK")
    """))


def test_pjit_train_step_multidevice():
    """The actual train step under a 4x2 (data, model) mesh: loss finite,
    params sharded per the rules, metrics replicated."""
    print(run_py("""
        import jax, jax.numpy as jnp
        from repro import configs as cfgs
        from repro.launch.mesh import make_local_mesh
        from repro.launch import steps as steps_lib
        from repro.models import lm
        from repro.optim import AdamWConfig, adamw_init
        from repro.optim.schedules import constant
        from repro.parallel import (param_specs, opt_state_specs,
                                    batch_specs, make_shardings)
        from repro.data import SyntheticLM

        cfg = cfgs.get_config("smollm-135m", reduced=True)
        mesh = make_local_mesh(4, 2)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt_cfg = AdamWConfig(lr=1e-3)
        opt = adamw_init(params, opt_cfg)
        pspecs = param_specs(params, mesh)
        pshard = make_shardings(pspecs, mesh)
        oshard = make_shardings(opt_state_specs(opt, pspecs, mesh), mesh)
        params = jax.tree_util.tree_map(jax.device_put, params, pshard)
        opt = jax.tree_util.tree_map(jax.device_put, opt, oshard)
        data = SyntheticLM(cfg.vocab, 32, 8)
        batch = dict(data.batch(0))
        bshard = make_shardings(batch_specs(batch, mesh), mesh)
        batch = jax.tree_util.tree_map(jax.device_put, batch, bshard)
        step = jax.jit(steps_lib.make_train_step(cfg, opt_cfg,
                                                 constant(1e-3)),
                       in_shardings=(pshard, oshard, bshard, None),
                       out_shardings=(pshard, oshard, None),
                       donate_argnums=(0, 1))
        p2, o2, m = step(params, opt, batch, jnp.int32(0))
        assert jnp.isfinite(m["loss"]), m
        # embed is sharded over (model, data) => 8 shards
        emb_sh = p2["embed"].sharding
        assert len(emb_sh.device_set) == 8
        print("PJIT_OK", float(m["loss"]))
    """))


def test_elastic_restore_across_topologies(tmp_path):
    """Checkpoint written from a 4x2 mesh reloads onto a 2x4 mesh
    (shrink/regrow path) with identical values."""
    print(run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import checkpoint as ck
        from repro.launch.mesh import make_local_mesh, make_mesh_for_shape
        from repro.parallel import param_specs, make_shardings
        from repro import configs as cfgs
        from repro.models import lm
        from repro.runtime import elastic_shrink_plan

        cfg = cfgs.get_config("smollm-135m", reduced=True)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        mesh1 = make_local_mesh(4, 2)
        sh1 = make_shardings(param_specs(params, mesh1), mesh1)
        placed = jax.tree_util.tree_map(jax.device_put, params, sh1)
        ck.save_checkpoint(r'{tmp_path}', 0, placed)

        new_shape = elastic_shrink_plan((4, 2), ("data", "model"), 1,
                                        devices_per_host=2)
        assert new_shape == (2, 2), new_shape
        mesh2 = make_mesh_for_shape(new_shape, ("data", "model"))
        sh2 = make_shardings(param_specs(params, mesh2), mesh2)
        restored, step = ck.restore_to_shardings(r'{tmp_path}', params, sh2)
        for a, b in zip(jax.tree_util.tree_leaves(restored),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))
        print("ELASTIC_OK")
    """))


def test_sequence_parallel_state_combine():
    """SP prefill: per-shard partial (S, z) combined with one psum equals
    the full-sequence state (associativity of the prefix state)."""
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_local_mesh
        from repro.core.linear_attention import (
            LinearState, sequence_parallel_state_combine)

        mesh = make_local_mesh(8, 1)
        L, m, dv = 64, 8, 4
        kf = jax.random.uniform(jax.random.PRNGKey(0), (L, m))
        v = jax.random.normal(jax.random.PRNGKey(1), (L, dv))
        s_full = kf.T @ v
        z_full = kf.sum(0)

        def shard_fn(kf_l, v_l):
            st = LinearState(kf_l.T @ v_l, kf_l.sum(0))
            st = sequence_parallel_state_combine(st, "data")
            return st.s, st.z

        s, z = shard_map(shard_fn, mesh=mesh,
                         in_specs=(P("data", None), P("data", None)),
                         out_specs=(P(None, None), P(None)))(kf, v)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_full),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(z), np.asarray(z_full),
                                   rtol=1e-5)
        print("SP_OK")
    """))


def test_sharded_slot_pool_decodes_token_identical():
    """ISSUE 3: the serving engine with a 2-device mesh (slot + staging
    pools device_put per serve_state_specs, constrained inside the jitted
    steps) streams token-identically to the unsharded engine, for both
    the PRF and the exact paged-KV kernels — and the pool really is
    sharded (2-device sharding on the batch axis)."""
    print(run_py("""
        import jax, numpy as np
        from repro import configs as cfgs
        from repro.launch.mesh import make_local_mesh
        from repro.models import lm
        from repro.serving import Request, ServingEngine

        for kind in ("darkformer", "exact"):
            cfg = cfgs.get_config("smollm-135m", reduced=True)
            cfg = cfgs.darkify(cfg, kind, cfg.attn.num_features)
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
            prompts = [jax.random.randint(jax.random.PRNGKey(40 + l),
                                          (l,), 0, cfg.vocab).tolist()
                       for l in (9, 17, 6)]

            streams = {}
            for mesh in (None, make_local_mesh(2, 1),
                         make_local_mesh(2, 2)):
                eng = ServingEngine(params, cfg, max_slots=4, max_len=48,
                                    chunk_tokens=6, mesh=mesh)
                uids = [eng.submit(Request(prompt=p, max_new_tokens=8))
                        for p in prompts]
                got = {r.uid: r.tokens for r in eng.run()}
                key = "none" if mesh is None else str(mesh.shape)
                streams[key] = [got[u] for u in uids]
                if mesh is not None:
                    ndev = len(eng.pool["pos"].sharding.device_set)
                    assert ndev == mesh.size, (kind, ndev)
            ref = streams.pop("none")
            for key, s in streams.items():
                assert s == ref, (kind, key)
        print("SHARDED_POOL_OK")
    """))
