"""Training step's share of the chip's bf16 peak: model FLOPs (6 N per
token plus the PRF attention, no recomputation) of the steps completed
in the traced window, over the window times the peak, in %."""


def read(ctx):
    steps = ctx.get("train_steps")
    if not steps:
        return None
    model = ctx["costs"]("model")
    flops = steps * ctx["tokens_per_step"] * model.train_flops_per_token(
        ctx["config"])
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
