"""One-process tools that set a training cell's numbers, run on the chip.

    python3 chipbench/calibrate.py seeds --workload W --seeds 1,2,3 \
        [--control]
    python3 chipbench/calibrate.py memory --workload W --seed S
    python3 chipbench/calibrate.py record --workload W --seed S \
        --seconds 1 --out F [--data DIR]

``seeds`` reads the numbers the check compares on many seeds (the
program's), and with ``--control`` the same numbers of the reference
computed in float8 in the program's place and of the reference with
half of each batch left out. ``memory`` prints the device's memory
counters beside the compiled step's own memory analysis. ``record``
keeps the profiler trace of a short traced window (the tests read one).
None is a benchmark run; each prints one JSON line per reading.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def _say(**kw):
    print(json.dumps(kw), flush=True)


def seeds(cell, args):
    import jax
    from chipbench import model, reference, train_cell, weights
    cj = cell["config"]
    dims = model.ref_dims(cj)
    for s in [int(x) for x in args.seeds.split(",")]:
        prog = train_cell.first_steps(cell, s)
        rows, cfg = prog["rows"], prog["cfg"]
        for k in ("step", "params", "opt", "batch"):
            del prog[k]
        w = weights.make(cfg, s)
        t = time.monotonic()
        ref = reference.train_steps(w, dims, cj["train"], rows)
        out = {"seed": s, "reference_s": time.monotonic() - t}

        def read(run):
            every = dict.fromkeys(train_cell.NUMBERS, math.inf)
            return {c["name"]: c["value"]
                    for c in train_cell.compare(run, ref, every)}

        out["program"] = read(prog)
        if args.control:
            out["control"] = read(reference.train_steps(
                w, dims, cj["train"], rows, quant="fp8"))
            out["half_batch"] = read(reference.train_steps(
                w, dims, cj["train"], rows[:, :rows.shape[1] // 2]))
        _say(**out)
        del w, ref, prog
        jax.clear_caches()


def memory(cell, args):
    import jax
    import jax.numpy as jnp
    from chipbench import train_cell
    cfg, step, params, opt, batch = train_cell.build(cell, args.seed)
    _, b = batch()
    compiled = step.lower(params, opt, b, jnp.int32(0)).compile()
    params, opt, m = step(params, opt, b, jnp.int32(0))
    jax.block_until_ready(m["loss"])
    ma = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes")
    _say(memory_analysis={f: getattr(ma, f, None) for f in fields},
         memory_stats=jax.devices()[0].memory_stats())


def record(cell, args):
    """Trace a short window of the cell and keep the trace file."""
    import shutil
    import jax
    from chipbench import core, trace, train_cell
    res, _ = train_cell.run(cell, args.seed, args.seconds, True,
                            core.Clock(T0), {}, core.CompileCounter(jax))
    src = trace.xplane_file(res["layer_ctx"]["trace_dir"])
    shutil.copy(src, args.out)
    _say(recorded=str(args.out), bytes=pathlib.Path(src).stat().st_size)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("tool", choices=("seeds", "memory", "record"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--data", default="",
                    help="a directory with its own BENCHMARK.json and "
                         "traffic/ (the tests' toy cells)")
    args = ap.parse_args(argv)
    from chipbench import core
    if args.data:
        data = pathlib.Path(args.data)
        man = core.load_json(data / "BENCHMARK.json")
        cell = core.load_cell(man, args.workload, ROOT, data / "traffic")
    else:
        man = core.manifest()
        cell = core.load_cell(man, args.workload)
    import jax
    core.check_devices(jax, cell["workload"]["chips"])
    core.setup_jax(jax)
    {"seeds": seeds, "memory": memory, "record": record}[args.tool](
        cell, args)


if __name__ == "__main__":
    main()
