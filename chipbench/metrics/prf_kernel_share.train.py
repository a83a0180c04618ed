"""Share of the PRF attention's device time a training step spends in
the causal mix's Pallas kernels (``prf_mix_fwd``, ``prf_mix_bwd_dq``,
``prf_mix_bwd_dkv``), in % of ``prf_attention_ms.train``: what is left
is XLA's (feature maps, casts, relayouts). None where no such kernel
ran."""
import re

from chipbench import scopes, trace

_KERNEL = re.compile(r"^prf_mix_(fwd|bwd)")


def is_kernel(tf_op) -> bool:
    """Whether an op's path holds one of the kernels' names."""
    for part in (tf_op or "").rstrip(":").split("/"):
        m = scopes._WRAPPED.match(part)
        if m and _KERNEL.match(m.group(1)):
            return True
    return False


def read(ctx):
    layer = scopes.layer_ms(ctx, ("prf_features", "prf_mix"))
    if not layer:
        return None
    ops, runs = scopes.step_ops(trace.xplane_file(ctx["trace_dir"]),
                                *ctx["span_ns"])
    ns = sum(op.end - op.start for op in ops if is_kernel(op.tf_op))
    if not runs or not ns:
        return None
    return 100.0 * ns / runs * 1e-6 / layer
