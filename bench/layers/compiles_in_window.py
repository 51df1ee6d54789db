"""compiles_in_window: XLA compiles (JAX's backend-compile events)
inside the measured window. Set-up warms every shape up, so this
should read 0."""


def read(ctx):
    return ctx.compiles_in_window
