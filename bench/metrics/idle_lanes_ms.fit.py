"""Chip milliseconds idle per protocol fit inside the lane engine's host
side (``apcvfl.lanes.*``, ``core/training.py:train_lanes``): per-lane
splits and stacking, the launch, the fit's one host sync and the
unstacking of the best parameters."""
import spanreduce


def read(ctx):
    return spanreduce.idle_ms_per_fit(ctx, ["apcvfl.lanes"])
