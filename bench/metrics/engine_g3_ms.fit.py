"""Device milliseconds per protocol fit of the lane engine's program
(``run_fit_k``) inside the g3 stage span (``apcvfl.g3``): the Eq. 5
distillation into the active party's encoder."""
import spanreduce


def read(ctx):
    return spanreduce.module_ms_per_fit(ctx, spanreduce.ENGINE, "apcvfl.g3")
