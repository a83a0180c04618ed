"""The chip benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU of this machine and
prints one JSON result line last on stdout (``--trace 0``: the cell's
end-to-end metrics; ``--trace 1``: its per-layer metrics, the device's
busy and window seconds, and a breakdown). Exits non-zero with no
result when JAX finds no TPU, too few chips, or a device kind without
published peaks in ``chipbench/peaks.json``.
"""
from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require_tpu: bool = True, manifest=None,
         traffic_dir=None):
    """Run one cell; ``require_tpu``, ``manifest`` and ``traffic_dir``
    exist for the CPU tests, which drive a run at toy sizes."""
    args = parse(argv)
    from chipbench import core
    man = core.load_json(pathlib.Path(manifest)) if manifest \
        else core.manifest()
    cell = core.load_cell(man, args.workload, core.ROOT, traffic_dir)
    import jax
    device = core.check_devices(jax, cell["workload"]["chips"],
                                require_tpu)
    if require_tpu:
        core.setup_jax(jax)
    compiles = core.CompileCounter(jax)
    clock = core.Clock(T0)
    res, checks = core.cell_runner(cell).run(
        cell, args.seed, args.seconds, bool(args.trace), clock, device,
        compiles)
    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    result = {"correct": res["correct"], "attempted": res["attempted"],
              "failed": res["failed"]}
    if args.trace:
        from chipbench import layers
        specs = core.cell_metrics(man, args.workload, "per_layer")
        metrics, dev, breakdown = layers.read(specs, res["layer_ctx"],
                                              device, require_tpu)
        device.update(dev)
        result["metrics"] = metrics
        result["breakdown"] = breakdown
    else:
        specs = core.cell_metrics(man, args.workload, "end_to_end")
        result["metrics"] = {m["name"]: {"value": res["metrics"][m["name"]],
                                         "unit": m["unit"]} for m in specs}
    result["device"] = device
    result.update(res.get("extra", {}))
    return core.emit(result, checks)


if __name__ == "__main__":
    from chipbench.core import BenchError
    try:
        main()
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        sys.exit(2)
