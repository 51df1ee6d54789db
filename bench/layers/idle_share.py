"""idle_share: percent of the traced call's window in which no
operation ran on the device (averaged over the cell's chips)."""


def read(ctx):
    t = ctx.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * t["idle_share"]
