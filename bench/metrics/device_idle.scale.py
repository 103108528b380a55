"""Idle share of the traced window on the idlest of the cell's chips."""


def read(ctx):
    tr = ctx["trace"]
    w = tr.window_s()
    return max(100.0 * (1.0 - tr.busy_s(d) / w) for d in ctx["devices"])
