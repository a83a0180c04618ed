"""The readings that set a ``train_moe`` cell's limits, run on the chip.

    python3 chipbench/calibrate_moe.py --workload W --seeds 1,2,3 \
        [--control-seeds 1,2]

For each seed, the numbers the check compares for the program; for each
control seed also those of the reference computed in float8 in the
program's place and of the reference with half of each batch left out.
It is ``calibrate.py seeds`` for this kind's runner and reference; not
a benchmark run. One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    import jax
    from chipbench import core, model, reference_granitemoe, weights
    from chipbench import train_moe_cell as cell_mod
    from chipbench.train_cell import NUMBERS
    cell = core.load_cell(core.manifest(), args.workload)
    core.check_devices(jax, cell["workload"]["chips"])
    core.setup_jax(jax)
    cj = cell["config"]
    dims = reference_granitemoe.dims_of(cj, model.ref_dims(cj))
    controls = {int(x) for x in args.control_seeds.split(",") if x}
    for s in [int(x) for x in args.seeds.split(",")]:
        prog = cell_mod.first_steps(cell, s)
        rows, cfg = prog["rows"], prog["cfg"]
        for k in ("step", "params", "opt", "batch"):
            del prog[k]
        w = weights.make(cfg, s)
        t = time.monotonic()
        ref = reference_granitemoe.train_steps(w, dims, cj["train"], rows)
        out = {"seed": s, "reference_s": time.monotonic() - t,
               "memory_peak_bytes": core.memory_peak_bytes(jax, 1),
               "moe_pairs_held": cell_mod.pairs_per_step(prog["loads"]),
               "moe_load_max_over_mean": cell_mod.load_max_over_mean(
                   prog["loads"])}

        def read(run):
            every = dict.fromkeys(NUMBERS, math.inf)
            return {c["name"]: c["value"]
                    for c in cell_mod.compare(run, ref, every)}

        out["program"] = read(prog)
        if s in controls:
            out["control"] = read(reference_granitemoe.train_steps(
                w, dims, cj["train"], rows, quant="fp8"))
            out["half_batch"] = read(reference_granitemoe.train_steps(
                w, dims, cj["train"], rows[:, :rows.shape[1] // 2]))
        print(json.dumps(out), flush=True)
        del w, ref, prog


if __name__ == "__main__":
    main()
