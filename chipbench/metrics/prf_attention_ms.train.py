"""Device ms a training step spends in the PRF attention: the ops under
the program's ``prf_features`` and ``prf_mix`` scopes (feature maps and
causal mix; forward, recomputation and backward)."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, ("prf_features", "prf_mix"))
