"""Device ms a training step spends at the two ends of the tied
vocabulary: the ops under the program's ``embed``, ``lm_head`` and
``loss`` scopes."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, ("embed", "lm_head", "loss"))
