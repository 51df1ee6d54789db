"""phase1_us_per_iter: device microseconds per ``while_loop`` trip of the
leaf ops under the engine's ``phase1`` scope: the ``sim_tick`` kernel and the
per-lane application of its masks (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_iter_us(ctx, "phase1")
