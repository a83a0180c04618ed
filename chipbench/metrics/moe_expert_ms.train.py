"""Device ms a training step spends in the experts' grouped matmuls:
the ops under the program's ``moe_experts`` scope, forward and
backward."""
from chipbench import moe_scopes


def read(ctx):
    return moe_scopes.moe_ms(ctx, ("moe_experts",))
