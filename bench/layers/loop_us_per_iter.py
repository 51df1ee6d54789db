"""loop_us_per_iter: device microseconds per ``while_loop`` trip of the
leaf ops under the engine's ``loop`` scope: the while_loop's cond and the
finished-lane masking of the carry (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_iter_us(ctx, "loop")
