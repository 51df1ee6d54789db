"""entry_idle_ms: milliseconds of the traced call in which the device
is idle outside any engine program, inside the program's entry-point
spans (``eudoxia/run/*``, ``eudoxia/fleet/*``) and no span nested in
them (``scopes.py``)."""


def read(ctx):
    split = getattr(ctx, "scopes", None)
    return split["entry_idle_s"] * 1e3 if split else None
