"""Device ms a training step spends routing in the expert layers: the
ops under the program's ``moe_router`` (router logits, top-k, gates,
aux loss), ``moe_dispatch`` (sort by expert, group sizes, the gather of
the pairs' rows) and ``moe_combine`` (back to the tokens, the gated
sum); forward, recomputation and backward."""
from chipbench import moe_scopes


def read(ctx):
    return moe_scopes.moe_ms(ctx, ("moe_router", "moe_dispatch",
                                   "moe_combine"))
