"""Share of the traced window in which the chip runs no operation."""


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * (1.0 - tr.busy_s(ctx["devices"][0]) / tr.window_s())
