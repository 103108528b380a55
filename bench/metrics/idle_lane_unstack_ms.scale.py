"""Milliseconds per lane-sharded fit in which the idlest of the cell's
chips idles inside ``apcvfl.lanes.unstack``
(``core/training.py:train_lanes``): the lane-sharded best-val parameters
taken apart into one tree a lane, replicated over the mesh."""
import spanreduce


def read(ctx):
    return spanreduce.idle_ms_per_fit(ctx, ["apcvfl.lanes.unstack"],
                                      dev=spanreduce.idlest(ctx))
