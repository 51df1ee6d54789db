"""loop_gap_us_per_iter: microseconds per ``while_loop`` trip in
which the engine program runs on the device but none of its leaf ops
does: launch and control-flow gaps inside the loop (``scopes.py``)."""
import scopes


def read(ctx):
    return scopes.per_iter_us(ctx, None)
