"""advance_us_per_iter: device microseconds per ``while_loop`` trip of the
leaf ops under the engine's ``advance`` scope: the next-event registers, the
jump and the utilisation integral (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_iter_us(ctx, "advance")
