"""Per-layer metrics of a traced run: each is read by
``metrics/<name>.py`` from the reduced trace and the run's counters. A
reader that finds nothing returns None and the metric is left out."""
from __future__ import annotations

from chipbench import core, trace


def read(specs, ctx, device, require_tpu=True):
    if not require_tpu:
        # a run off the chip has no device trace to read
        return {}, {}, {}
    tr = trace.load(ctx["trace_dir"])
    lo, hi = tr.span_bounds()
    ctx = dict(ctx, trace=tr, peaks=core.peaks(device["kind"]),
               costs=lambda name: core.load_module("costs", name),
               span_ns=(lo, hi))
    metrics = {}
    for m in specs:
        v = core.load_module("metrics", m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"busy_s": tr.busy_ns(lo, hi) * 1e-9,
           "window_s": ctx["window_s"]}
    breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
    return metrics, dev, breakdown
