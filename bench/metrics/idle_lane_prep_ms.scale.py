"""Milliseconds per lane-sharded fit in which the idlest of the cell's
chips idles inside ``apcvfl.lanes.prep`` (``core/training.py:_prep_lanes``):
the host's per-lane splits and the padded stack on the first chip."""
import spanreduce


def read(ctx):
    return spanreduce.idle_ms_per_fit(ctx, ["apcvfl.lanes.prep"],
                                      dev=spanreduce.idlest(ctx))
