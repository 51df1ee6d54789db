"""iters_per_call: trips of the engine's ``while_loop`` in the traced
call, counted as launches of the ``sim_tick`` kernel (one per trip)."""


def read(ctx):
    n = ctx.trace["kernel_launches"] if ctx.trace else 0
    return n or None
