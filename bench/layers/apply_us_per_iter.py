"""apply_us_per_iter: device microseconds per ``while_loop`` trip of the
leaf ops under the engine's ``apply`` scope: ``apply_decision``, the grants
written onto the container and pipeline tables (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_iter_us(ctx, "apply")
