"""scheduler_us_per_iter: device microseconds per ``while_loop`` trip of the
leaf ops under the engine's ``scheduler`` scope: the policy's decision loop (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_iter_us(ctx, "scheduler")
