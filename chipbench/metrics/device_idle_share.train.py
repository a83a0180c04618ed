"""Device idle share of a training window: 1 - (union of device-op
intervals / the traced window, first to last host span), in %."""


def read(ctx):
    if "train_steps" not in ctx:
        return None
    lo, hi = ctx["span_ns"]
    if hi <= lo:
        return None
    return 100.0 * (1.0 - ctx["trace"].busy_ns(lo, hi) / (hi - lo))
