"""Chip milliseconds idle per protocol fit inside private set intersection
(``apcvfl.psi``, ``core/psi.py``): the host hashing and matching ids while
the chip waits."""
import spanreduce


def read(ctx):
    return spanreduce.idle_ms_per_fit(ctx, ["apcvfl.psi"])
