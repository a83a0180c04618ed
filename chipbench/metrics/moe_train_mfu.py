"""A mixture-of-experts training step's share of the chip's bf16 peak:
model FLOPs (``costs/granitemoe.py``: 6 x the active parameters, the
experts' from the pairs the step routed to the experts held, plus the
PRF attention; no recomputation) of the steps completed in the traced
window, over the window times the peak, in %."""


def read(ctx):
    steps, pairs = ctx.get("train_steps"), ctx.get("moe_pairs_held")
    if not steps or not pairs:
        return None
    flops = ctx["costs"]("granitemoe").train_flops(
        ctx["config"], ctx["tokens_per_step"], pairs)
    return 100.0 * steps * flops / (ctx["window_s"]
                                    * ctx["peaks"]["bf16_flops_per_s"])
