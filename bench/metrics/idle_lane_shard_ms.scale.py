"""Milliseconds per lane-sharded fit in which the idlest of the cell's
chips idles inside ``apcvfl.lanes.shard``
(``core/training.py:_shard_lanes``): the stacked lanes spread from the
first chip over the mesh."""
import spanreduce


def read(ctx):
    return spanreduce.idle_ms_per_fit(ctx, ["apcvfl.lanes.shard"],
                                      dev=spanreduce.idlest(ctx))
