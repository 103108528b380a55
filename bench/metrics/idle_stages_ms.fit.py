"""Chip milliseconds idle per protocol fit in the self time of the five
stage spans of ``core/pipeline.py:run_apcvfl_replicated`` (g1, exchange,
g2, g3, probe): parameter init and input building between the lane
engine's calls, outside ``apcvfl.lanes.*``."""
import spanreduce

STAGES = ["apcvfl.g1", "apcvfl.exchange", "apcvfl.g2", "apcvfl.g3",
          "apcvfl.probe"]


def read(ctx):
    return spanreduce.idle_ms_per_fit(ctx, STAGES)
