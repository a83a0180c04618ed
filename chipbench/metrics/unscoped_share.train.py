"""Share of a training step's device op time under none of the
program's layer scopes, in %: what the per-layer ms metrics leave
out."""
from chipbench import scopes


def read(ctx):
    return scopes.unscoped_share(ctx)
