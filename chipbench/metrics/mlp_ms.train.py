"""Device ms a training step spends in the MLP: the ops under the
program's ``mlp`` scope (pre-norm, FFN, residual)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, ("mlp",))
