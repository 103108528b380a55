"""Device milliseconds per lane-sharded fit in ``run_fit_k``, on the
busiest of the cell's chips."""
PROGRAM = "run_fit_k"


def read(ctx):
    tr, fits = ctx["trace"], ctx["window"]["fits"]
    devs = [d for d in ctx["devices"] if tr.module_count(d, PROGRAM)]
    if not devs:
        return None
    return max(tr.module_s(d, PROGRAM) for d in devs) / fits * 1e3
