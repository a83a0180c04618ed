import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede any jax-importing import: jax locks the device count on
# first backend init. Only the dry-run gets 512 placeholder devices.

import argparse        # noqa: E402
import dataclasses     # noqa: E402
import functools       # noqa: E402
import json            # noqa: E402
import re              # noqa: E402
import time            # noqa: E402
import traceback       # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs as cfgs                      # noqa: E402
from repro.configs.base import SHAPES                  # noqa: E402
from repro.launch import mesh as mesh_lib              # noqa: E402
from repro.launch import steps as steps_lib            # noqa: E402
from repro.models import lm                            # noqa: E402
from repro.optim import AdamWConfig, adamw_init        # noqa: E402
from repro.optim.schedules import cosine_warmup        # noqa: E402
from repro.parallel import (param_specs, opt_state_specs, batch_specs,
                            serve_state_specs, make_shardings)  # noqa: E402

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware:
  * every input is a ShapeDtypeStruct (no allocation),
  * .lower().compile() must succeed on the 16x16 single-pod mesh AND the
    2x16x16 multi-pod mesh,
  * memory_analysis / cost_analysis / the collective schedule parsed from
    the optimized HLO feed EXPERIMENTS.md §Dry-run and §Roofline.

Results are cached as JSON per cell under experiments/dryrun/.
"""

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}


def _shape_bytes(shape_str: str) -> int:
    """'bf16[16,4096,512]{...}' -> bytes. Tuples handled by the caller."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in optimized HLO.

    Returns {op_kind: bytes, "total": bytes, "count": n}. Per-device
    payload approximated by the op's result shape (received bytes).
    """
    out = {k: 0 for k in _COLLECTIVES}
    count = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (\([^)]*\)|[a-z0-9]+\[[0-9,]*\]\S*) ([a-z\-]+)", ls)
        if not m:
            continue
        shape_s, op = m.groups()
        if op not in _COLLECTIVES:
            continue
        if op == "all-to-all" and "-start" in ls:
            pass
        count += 1
        if shape_s.startswith("("):
            inner = re.findall(r"[a-z0-9]+\[[0-9,]*\]", shape_s)
            b = sum(_shape_bytes(s) for s in inner)
        else:
            b = _shape_bytes(shape_s)
        out[op] += b
    # async pairs (xxx-start / xxx-done) show the payload on -start only;
    # the regex above already counts each op name once per line.
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["count"] = count
    return out


def hbm_traffic_bytes(cost: dict) -> float:
    return float(cost.get("bytes accessed", 0.0))


def build_cell(arch: str, shape_name: str, mesh, kernel: str,
               remat: str = "dots", cfg=None, preset: str = "2d",
               shard_features: bool = False):
    """Returns (lower_fn, meta) for the cell; lower_fn() -> jax.Lowered."""
    if cfg is None:
        cfg = cfgs.get_config(arch)
        if kernel:
            cfg = cfgs.darkify(cfg, kernel, cfg.attn.num_features)
        cfg = dataclasses.replace(cfg, remat=remat)
    ok, why = cfgs.cell_supported(cfg, shape_name)
    if not ok:
        return None, {"skipped": why}
    sh = SHAPES[shape_name]
    kind = sh["kind"]
    batch = cfgs.input_specs(cfg, shape_name)
    pshape = jax.eval_shape(
        functools.partial(lm.init_params, cfg=cfg), jax.random.PRNGKey(0))
    pspecs = param_specs(pshape, mesh, moe=cfg.moe is not None,
                         preset=preset, shard_features=shard_features,
                         overrides=cfg.sharding_overrides)
    pshard = make_shardings(pspecs, mesh)
    bshard = make_shardings(batch_specs(batch, mesh, preset=preset), mesh)
    meta = {"arch": arch, "shape": shape_name, "kind": kind,
            "kernel": cfg.attn.kind,
            "mesh": dict(zip(mesh.axis_names,
                             [mesh.shape[a] for a in mesh.axis_names])),
            "param_count": sum(
                int(x.size) for x in jax.tree_util.tree_leaves(pshape)),
            "seq_len": sh["seq_len"], "global_batch": sh["global_batch"]}

    if kind == "train":
        opt_cfg = AdamWConfig(factored_second_moment=(
            meta["param_count"] > 1e11))
        oshape = jax.eval_shape(
            functools.partial(adamw_init, cfg=opt_cfg), pshape)
        oshard = make_shardings(opt_state_specs(oshape, pspecs, mesh), mesh)
        step_fn = steps_lib.make_train_step(
            cfg, opt_cfg, cosine_warmup(3e-4, 100, 10_000))
        jitted = jax.jit(step_fn,
                         in_shardings=(pshard, oshard, bshard, None),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1))

        def lower():
            return jitted.lower(pshape, oshape, batch,
                                jax.ShapeDtypeStruct((), jnp.int32))
        return lower, meta

    if kind == "prefill":
        step_fn = steps_lib.make_prefill_step(cfg, max_len=sh["seq_len"])
        jitted = jax.jit(step_fn, in_shardings=(pshard, bshard))

        def lower():
            return jitted.lower(pshape, batch)
        return lower, meta

    # decode
    b = sh["global_batch"]
    sshape = jax.eval_shape(
        functools.partial(lm.init_serve_state, cfg, b, sh["seq_len"]))
    sshard = make_shardings(serve_state_specs(sshape, mesh), mesh)
    step_fn = steps_lib.make_decode_step(cfg)
    jitted = jax.jit(step_fn,
                     in_shardings=(pshard, bshard["token"], sshard),
                     out_shardings=(None, sshard),
                     donate_argnums=(2,))

    def lower():
        return jitted.lower(pshape, batch["token"], sshape)
    return lower, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool, kernel: str,
             outdir: str, force: bool = False, remat: str = "dots",
             tag: str = "", preset: str = "2d",
             shard_features: bool = False) -> dict:
    mesh_name = "multipod" if multi_pod else "pod"
    os.makedirs(outdir, exist_ok=True)
    fname = os.path.join(
        outdir, f"{arch}__{shape_name}__{mesh_name}"
        + (f"__{tag}" if tag else "") + ".json")
    if os.path.exists(fname) and not force:
        with open(fname) as f:
            return json.load(f)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rec: dict = {}
    try:
        lower_fn, meta = build_cell(arch, shape_name, mesh, kernel, remat,
                                    preset=preset,
                                    shard_features=shard_features)
        rec.update(meta)
        if lower_fn is None:
            rec["status"] = "skipped"
        else:
            t0 = time.time()
            with jax.set_mesh(mesh):
                lowered = lower_fn()
            rec["lower_s"] = round(time.time() - t0, 2)
            t0 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t0, 2)
            try:
                mem = compiled.memory_analysis()
                rec["memory"] = {
                    k: int(getattr(mem, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes",
                              "generated_code_size_in_bytes")
                    if hasattr(mem, k)}
            except Exception as e:          # CPU backend gaps are fine
                rec["memory"] = {"error": str(e)}
            try:
                cost = compiled.cost_analysis()
                if isinstance(cost, list):
                    cost = cost[0]
                rec["cost"] = {k: float(v) for k, v in cost.items()
                               if isinstance(v, (int, float))
                               and k in ("flops", "bytes accessed",
                                         "transcendentals",
                                         "utilization operand 0 {}",
                                         "optimal_seconds")}
                rec["flops"] = float(cost.get("flops", 0.0))
                rec["bytes_accessed"] = float(cost.get("bytes accessed",
                                                       0.0))
            except Exception as e:
                rec["cost"] = {"error": str(e)}
            try:
                hlo = compiled.as_text()
                rec["collectives"] = collective_bytes(hlo)
                rec["hlo_bytes"] = len(hlo)
            except Exception as e:
                rec["collectives"] = {"error": str(e)}
            rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def _extract_costs(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collective_total": float(coll["total"]),
            "collectives": coll}


def run_cost_probe(arch: str, shape_name: str, kernel: str, outdir: str,
                   force: bool = False, remat: str = "dots",
                   tag: str = "", preset: str = "2d",
                   shard_features: bool = False,
                   features: int = 0) -> dict:
    """Exact per-device cost extrapolation for scanned stacks.

    XLA's HloCostAnalysis counts a while-loop body ONCE (verified: a scan
    of 8 matmuls reports 1 matmul of flops), so the scanned-stack module
    costs undercount in-loop work by ~n_units x. This probe lowers the
    same cell UNROLLED at 1 and 2 pattern-units (+ remainder layers) on the
    single-pod mesh; the unit difference is the exact per-unit cost and

        total = outside + n_units * unit,   outside = probe1 - unit

    which reconstructs flops / bytes / collective-bytes for the full
    depth. Residual undercount: bodies of *inner* time scans (the chunked
    linear-attention scan, RWKV's wkv scan) — ~1-3% of flops (see
    EXPERIMENTS.md §Roofline notes).
    """
    os.makedirs(outdir, exist_ok=True)
    fname = os.path.join(
        outdir, f"{arch}__{shape_name}__probe"
        + (f"__{tag}" if tag else "") + ".json")
    if os.path.exists(fname) and not force:
        with open(fname) as f:
            return json.load(f)
    rec: dict = {"arch": arch, "shape": shape_name, "probe": True,
                 "tag": tag, "preset": preset,
                 "shard_features": shard_features}
    try:
        mesh = mesh_lib.make_production_mesh(multi_pod=False)
        cfg_full = cfgs.get_config(arch)
        if kernel:
            cfg_full = cfgs.darkify(cfg_full, kernel,
                                    features or cfg_full.attn.num_features)
        cfg_full = dataclasses.replace(cfg_full, remat=remat)
        plen = len(cfg_full.block_pattern)
        rem = cfg_full.n_rem
        probes = []
        for units in (1, 2):
            cfg_p = dataclasses.replace(
                cfg_full, n_layers=plen * units + rem, scan_layers=False)
            lower_fn, meta = build_cell(arch, shape_name, mesh, kernel,
                                        remat, cfg=cfg_p, preset=preset,
                                        shard_features=shard_features)
            if lower_fn is None:
                rec["status"] = "skipped"
                rec["skipped"] = meta.get("skipped", "")
                with open(fname, "w") as f:
                    json.dump(rec, f, indent=1)
                return rec
            t0 = time.time()
            with jax.set_mesh(mesh):
                compiled = lower_fn().compile()
            probes.append(_extract_costs(compiled))
            probes[-1]["compile_s"] = round(time.time() - t0, 2)
        u = cfg_full.n_units
        extrap = {}
        for k in ("flops", "bytes_accessed", "collective_total"):
            unit = max(probes[1][k] - probes[0][k], 0.0)
            outside = max(probes[0][k] - unit, 0.0)
            extrap[k] = outside + u * unit
            extrap[k + "_unit"] = unit
            extrap[k + "_outside"] = outside
        rec.update({"status": "ok", "n_units": u, "probes": probes,
                    "extrapolated": extrap})
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    with open(fname, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod",
                                                      "both"])
    ap.add_argument("--kernel", default="darkformer")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="run the unrolled 2-point cost probe (exact "
                         "flops/bytes/collectives for §Roofline)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--preset", default="2d", choices=["2d", "fsdp"])
    ap.add_argument("--shard-features", action="store_true")
    ap.add_argument("--features", type=int, default=0,
                    help="override PRF feature count m (probe only)")
    ap.add_argument("--tag", default="")
    ap.add_argument("--outdir", default="experiments/dryrun")
    args = ap.parse_args()

    archs = cfgs.ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = (["pod", "multipod"] if args.mesh == "both"
              else [args.mesh])
    n_ok = n_skip = n_err = 0
    if args.probe:
        for arch in archs:
            for shape in shapes:
                t0 = time.time()
                rec = run_cost_probe(arch, shape, args.kernel, args.outdir,
                                     args.force, args.remat, args.tag,
                                     args.preset, args.shard_features,
                                     args.features)
                status = rec.get("status")
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_err += status == "error"
                line = (f"[probe {arch} x {shape}] {status} "
                        f"({time.time()-t0:.1f}s)")
                if status == "ok":
                    e = rec["extrapolated"]
                    line += (f" flops={e['flops']:.3e}"
                             f" coll={e['collective_total']:.3e}B")
                elif status == "error":
                    line += " :: " + rec.get("error", "")[:200]
                print(line, flush=True)
        print(f"probe summary: ok={n_ok} skipped={n_skip} errors={n_err}")
        raise SystemExit(1 if n_err else 0)
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                t0 = time.time()
                rec = run_cell(arch, shape, mesh_name == "multipod",
                               args.kernel, args.outdir, args.force,
                               args.remat, args.tag, args.preset,
                               args.shard_features)
                status = rec.get("status")
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_err += status == "error"
                line = (f"[{arch} x {shape} x {mesh_name}] {status} "
                        f"({time.time()-t0:.1f}s)")
                if status == "ok":
                    line += (f" flops={rec.get('flops', 0):.3e}"
                             f" coll={rec.get('collectives', {}).get('total', 0):.3e}B"
                             f" compile={rec.get('compile_s')}s")
                elif status == "error":
                    line += " :: " + rec.get("error", "")[:200]
                print(line, flush=True)
    print(f"dryrun summary: ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
