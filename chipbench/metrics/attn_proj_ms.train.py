"""Device ms a training step spends in the attention's projections: the
ops under the program's ``attn_in`` (pre-norm, q/k/v, RoPE) and
``attn_out`` (head merge, wo, residual) scopes."""
from chipbench import scopes


def read(ctx):
    return scopes.layer_ms(ctx, ("attn_in", "attn_out"))
