"""Device time of an expert layer's parts: the program names them with
``jax.named_scope``s inside its ``mlp`` scope (``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``). ``scopes.py``
attributes those ops to ``mlp``; here each op of the step's executions
goes to the innermost of these names on its path, or to none.

The grouped matmuls are XLA's ragged-dot custom calls. XLA makes them
after the scopes are set and gives them no path: their ``tf_op`` is
their own name (``ragged-dot-none:``), so ``scopes.py`` counts them as
unscoped. Here an op named ``ragged-dot...`` goes to ``moe_experts``:
the expert layer's grouped matmuls are the step's only ones."""
from __future__ import annotations

import collections

from chipbench import scopes, trace

MOE = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")


def moe_scope_of(tf_op):
    """The innermost of ``MOE`` on an op's path, or None."""
    found = None
    for part in (tf_op or "").rstrip(":").split("/"):
        m = scopes._WRAPPED.match(part)
        if m and m.group(1) in MOE:
            found = m.group(1)
    return found


def _read(ctx):
    if "moe_scopes" not in ctx:
        ops, runs = scopes.step_ops(trace.xplane_file(ctx["trace_dir"]),
                                    *ctx["span_ns"])
        tot = collections.Counter()
        for op in ops:
            name = moe_scope_of(op.tf_op) or (
                "moe_experts" if trace.short_name(op.name).startswith(
                    "ragged-dot") else None)
            if name:
                tot[name] += op.end - op.start
        ctx["moe_scopes"] = (dict(tot), runs)
    return ctx["moe_scopes"]


def moe_ms(ctx, names):
    """Device ms a step under the scopes ``names``; None where none of
    them is in the trace."""
    tot, runs = _read(ctx)
    if not runs or not any(n in tot for n in names):
        return None
    return sum(tot.get(n, 0.0) for n in names) / runs * 1e-6
