"""Device milliseconds per protocol fit in the lane engine's programs
(``run_fit_k``, ``core/training.py``), on the cell's chip."""
PROGRAM = "run_fit_k"


def read(ctx):
    tr, fits = ctx["trace"], ctx["window"]["fits"]
    dev = ctx["devices"][0]
    if not tr.module_count(dev, PROGRAM):
        return None
    return tr.module_s(dev, PROGRAM) / fits * 1e3
