"""Device time of the training step by the program's layer scopes.

The program runs each layer of its step under a ``jax.named_scope``;
XLA keeps the scope in every op's name, and the profiler's trace gives
each device op that name as the ``tf_op`` stat of its event metadata,
for example ``jit(train_step)/transpose(jvp())/while/body/closed_call/
checkpoint/prf_mix/...cqm,...ckm->...cqk/dot_general:``.
``jax.profiler.ProfileData`` gives an event's times but not its
metadata's stats, so this module reads the ``.xplane.pb`` file itself,
with a schema for the subset of the profiler's ``XSpace`` it needs,
built at run time.

The step program is the ``XLA Modules`` event name with the most device
time in the traced window; only its executions that lie whole inside the
window and ended before the capture did count. Each op of those
executions, other than the ops that only hold others
(``trace._CONTAINERS``), is attributed to the innermost scope of
``SCOPES`` on its path, or to ``unscoped``.
"""
from __future__ import annotations

import collections
import functools
import re

from chipbench import trace

DEVICE = "/device:TPU:0"
# the program's layer scopes, as the per-layer metrics name them
SCOPES = ("embed", "attn_in", "prf_features", "prf_mix", "attn_out",
          "mlp", "lm_head", "loss", "optimizer")
UNSCOPED = "unscoped"
# a path component with its transform wrappers: "transpose(jvp(mlp))"
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")

Op = collections.namedtuple("Op", "name start end tf_op")


# The fields of the profiler's ``xplane.proto`` read here, by their wire
# numbers (a file's other fields are skipped): "*" repeats a field, and
# "{...}" is a map from int64 ids.
_XPLANE = {
    "XSpace": [("planes", 1, "*XPlane")],
    "XPlane": [("id", 1, "int64"), ("name", 2, "string"),
               ("lines", 3, "*XLine"),
               ("event_metadata", 4, "{XEventMetadata}"),
               ("stat_metadata", 5, "{XStatMetadata}")],
    "XLine": [("id", 1, "int64"), ("name", 2, "string"),
              ("timestamp_ns", 3, "int64"), ("events", 4, "*XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64")],
    "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                       ("stats", 5, "*XStat")],
    "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
    "XStat": [("metadata_id", 1, "int64"), ("str_value", 5, "string"),
              ("ref_value", 7, "uint64")],
}


@functools.cache
def xspace_class():
    """The message class of an ``.xplane.pb`` file, built from
    ``_XPLANE`` at run time."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    pkg = "chipbench_xplane"
    fd = descriptor_pb2.FileDescriptorProto(
        name=f"{pkg}.proto", package=pkg, syntax="proto3")

    def add(msg, name, number, kind):
        f = msg.field.add(name=name, number=number, label=F.LABEL_OPTIONAL)
        if kind.startswith("*"):
            f.label, kind = F.LABEL_REPEATED, kind[1:]
        if kind.startswith("{"):
            entry = msg.nested_type.add(
                name="".join(w.title() for w in name.split("_")) + "Entry")
            entry.options.map_entry = True
            add(entry, "key", 1, "int64")
            add(entry, "value", 2, kind[1:-1])
            f.label = F.LABEL_REPEATED
            f.type, f.type_name = F.TYPE_MESSAGE, \
                f".{pkg}.{msg.name}.{entry.name}"
        elif kind in _XPLANE:
            f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{kind}"
        else:
            f.type = getattr(F, f"TYPE_{kind.upper()}")

    for name, fields in _XPLANE.items():
        msg = fd.message_type.add(name=name)
        for field in fields:
            add(msg, *field)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"{pkg}.XSpace"))


def device_lines(path) -> dict:
    """The events of the ``XLA Modules`` and ``XLA Ops`` lines of the
    first TPU's plane, as ``Op(name, start, end, tf_op)`` with times in
    ns on the clock of ``trace.load`` (``tf_op`` None where the op has
    none)."""
    space = xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {"XLA Modules": [], "XLA Ops": []}
    for plane in space.planes:
        if plane.name != DEVICE:
            continue
        tf_op = {k for k, m in plane.stat_metadata.items()
                 if m.name == "tf_op"}
        meta = {}
        for k, m in plane.event_metadata.items():
            op = None
            for s in m.stats:
                if s.metadata_id in tf_op:
                    op = s.str_value or (
                        plane.stat_metadata[s.ref_value].name
                        if s.ref_value in plane.stat_metadata else None)
            meta[k] = (m.name, op)
        for line in plane.lines:
            if line.name not in out:
                continue
            for e in line.events:
                name, op = meta.get(e.metadata_id, ("", None))
                start = line.timestamp_ns + e.offset_ps / 1000.0
                out[line.name].append(
                    Op(name, start, start + e.duration_ps / 1000.0, op))
        break
    return out


def scope_of(tf_op) -> str:
    """The innermost of ``SCOPES`` on an op's path, or ``unscoped``."""
    found = UNSCOPED
    for part in (tf_op or "").rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            found = m.group(1)
    return found


def step_ops(path, lo, hi):
    """The ops of the step program's executions that lie whole inside
    [lo, hi] and ended before the capture did, without the ops that only
    hold others, and the number of those executions."""
    lines = device_lines(path)
    mods = [e for e in lines["XLA Modules"] if e.end > lo and e.start < hi]
    busy = collections.Counter()
    for e in mods:
        busy[e.name] += min(e.end, hi) - max(e.start, lo)
    if not busy:
        return [], 0
    step = busy.most_common(1)[0][0]
    # the capture stops with a step in flight: an execution still open
    # at its end is cut short, though it may end inside the window
    cut = max(e.end for e in lines["XLA Modules"] + lines["XLA Ops"])
    runs = [(e.start, e.end) for e in mods if e.name == step
            and lo <= e.start and e.end <= hi and e.end < cut]
    ops = [op for op in lines["XLA Ops"]
           if trace.short_name(op.name).split(" ")[-1]
           not in trace._CONTAINERS
           and any(s <= op.start and op.end <= t for s, t in runs)]
    return ops, len(runs)


def step_scopes(path, lo, hi):
    """Device ns of ``step_ops`` by scope, and the number of
    executions."""
    ops, runs = step_ops(path, lo, hi)
    tot = collections.Counter()
    for op in ops:
        tot[scope_of(op.tf_op)] += op.end - op.start
    return dict(tot), runs


def _read(ctx):
    """``step_scopes`` of the run's trace and window, read once per
    ``ctx`` (every metric of a run shares one)."""
    if "step_scopes" not in ctx:
        lo, hi = ctx["span_ns"]
        ctx["step_scopes"] = step_scopes(
            trace.xplane_file(ctx["trace_dir"]), lo, hi)
    return ctx["step_scopes"]


def layer_ms(ctx, names):
    """Device ms a step of the ops under the scopes ``names``: forward,
    recomputation and backward. None where none of them is in the
    trace."""
    tot, runs = _read(ctx)
    if not runs or not any(n in tot for n in names):
        return None
    return sum(tot.get(n, 0.0) for n in names) / runs * 1e-6


def unscoped_share(ctx):
    """The step's op time under no scope, in % of all of its op time."""
    tot, runs = _read(ctx)
    whole = sum(tot.values())
    if not runs or whole <= 0:
        return None
    return 100.0 * tot.get(UNSCOPED, 0.0) / whole
