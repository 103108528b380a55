"""Chip milliseconds idle per protocol fit inside ``apcvfl.lanes.unstack``
(``core/training.py:train_lanes``): each shape group's stacked best-val
parameters taken apart into one tree a lane."""
import spanreduce


def read(ctx):
    return spanreduce.idle_ms_per_fit(ctx, ["apcvfl.lanes.unstack"])
