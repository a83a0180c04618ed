"""The experts' grouped matmuls' share of their roofline, in %: the
time their FLOPs and bytes take at the chip's peaks (the larger of the
two, ``costs/granitemoe.py``, from the pairs the step routed to the
experts held) over their device time a step (``moe_expert_ms.train``).
None where the run counted no pairs."""
from chipbench import moe_scopes


def read(ctx):
    pairs = ctx.get("moe_pairs_held")
    ms = moe_scopes.moe_ms(ctx, ("moe_experts",))
    if not pairs or not ms:
        return None
    costs, pk = ctx["costs"]("granitemoe"), ctx["peaks"]
    cj = ctx["config"]
    roof_s = max(costs.gmm_flops(cj, pairs) / pk["bf16_flops_per_s"],
                 costs.gmm_bytes(cj, pairs) / pk["hbm_bytes_per_s"])
    return 100.0 * roof_s / (ms * 1e-3)
