"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's
numbers: device busy time, the longest device ops, and the device's idle
gaps attributed to the host span they fall in.

The trace is read with ``jax.profiler.ProfileData``. Device operations
are the events of the ``XLA Ops`` line of the first TPU plane; host
spans are the benchmark's own ``TraceAnnotation`` events on the host
plane. Both share one clock.
"""
from __future__ import annotations

import collections
import glob
import pathlib
import re

SPANS = ("train_step", "window")
# the opcode after the result shapes: "= f32[..]{..} fusion(" or
# "= (f32[..], ..) custom-call("
_OPCODE = re.compile(r"[\]\}\)]\s+([a-z][a-z\-]*)\(")
# ops that hold other ops (their time is their body's)
_CONTAINERS = ("while", "conditional", "call")


def xplane_file(trace_dir) -> str:
    files = sorted(glob.glob(str(pathlib.Path(trace_dir) / "**" /
                                 "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


Event = collections.namedtuple("Event", "name start end")


def _events(line):
    return [Event(e.name, float(e.start_ns),
                  float(e.start_ns + e.duration_ns)) for e in line.events]


class Trace:
    """The parts of one trace the metrics read."""

    def __init__(self, ops, spans):
        self.ops = sorted(ops, key=lambda e: e.start)
        self.spans = sorted(spans, key=lambda e: e.start)

    # -- device time ----------------------------------------------------
    def busy_intervals(self, lo=None, hi=None):
        """Union of device-op intervals, clipped to [lo, hi]."""
        out = []
        for e in self.ops:
            s, t = e.start, e.end
            if lo is not None:
                s = max(s, lo)
            if hi is not None:
                t = min(t, hi)
            if t <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t)
            else:
                out.append([s, t])
        return out

    def span_bounds(self):
        """The traced window on the trace's clock: the benchmark's
        ``window`` span, else first host-span start to last end."""
        win = [e for e in self.spans if e.name == "window"]
        if win:
            return win[0].start, win[0].end
        if not self.spans:
            return (self.ops[0].start, self.ops[-1].end) if self.ops \
                else (0.0, 0.0)
        return self.spans[0].start, max(e.end for e in self.spans)

    def busy_ns(self, lo=None, hi=None) -> float:
        return sum(t - s for s, t in self.busy_intervals(lo, hi))

    def _inside(self, events):
        lo, hi = self.span_bounds()
        return [e for e in events if lo <= e.start and e.end <= hi]

    def top_ops(self, n=10):
        """The device ops that took most time (ops that only hold
        others, such as a layer loop, are left out)."""
        tot = collections.Counter()
        for e in self._inside(self.ops):
            name = short_name(e.name)
            if name.split(" ")[-1] in _CONTAINERS:
                continue
            tot[name] += e.end - e.start
        return [[k, v * 1e-9] for k, v in tot.most_common(n)]

    def idle_gaps(self, n=10):
        """The longest idle gaps of the device inside the traced window,
        each named by the host span its midpoint falls in."""
        lo, hi = self.span_bounds()
        busy = self.busy_intervals(lo, hi)
        spans = [e for e in self.spans if e.name != "window"]
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, t in gaps[:n]:
            mid = 0.5 * (s + t)
            name = "none"
            for e in spans:
                if e.start <= mid <= e.end:
                    name = e.name
                    break
            out.append([name, (t - s) * 1e-9])
        return out


def load(trace_dir) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_file(trace_dir))
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e.name in SPANS]
    return Trace(ops, spans)


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12 fusion``."""
    head = name.split(" = ", 1)
    m = _OPCODE.search(name)
    op = m.group(1) if m else ""
    return f"{head[0].lstrip('%')} {op}".strip()

