"""Peak bytes in use on device 0 after the window (the device's own
counter), in GB."""


def read(ctx):
    return ctx["memory_peak"][0] / 1e9
