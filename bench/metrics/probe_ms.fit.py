"""Device milliseconds per protocol fit in the k-fold probe's program
(``_fit_predict_folds_many``, ``core/classifier.py``)."""
PROGRAM = "_fit_predict_folds_many"


def read(ctx):
    tr, fits = ctx["trace"], ctx["window"]["fits"]
    dev = ctx["devices"][0]
    if not tr.module_count(dev, PROGRAM):
        return None
    return tr.module_s(dev, PROGRAM) / fits * 1e3
