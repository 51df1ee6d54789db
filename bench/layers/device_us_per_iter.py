"""device_us_per_iter: microseconds the device is busy inside the engine
program per ``while_loop`` trip, in the traced call."""


def read(ctx):
    t = ctx.trace
    if not t or not t["kernel_launches"] or not t["engine_busy_s"]:
        return None
    return t["engine_busy_s"] / t["kernel_launches"] * 1e6
