"""Device ms a training step spends in the optimizer: the ops under the
program's ``optimizer`` scope (clipping, schedule, AdamW update)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, ("optimizer",))
