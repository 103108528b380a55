"""Device milliseconds per protocol fit of the lane engine's program
(``run_fit_k``) inside the g1 stage span (``apcvfl.g1``): both parties'
local autoencoders."""
import spanreduce


def read(ctx):
    return spanreduce.module_ms_per_fit(ctx, spanreduce.ENGINE, "apcvfl.g1")
