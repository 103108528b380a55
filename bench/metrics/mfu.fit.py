"""The whole fit's share of the chip's peak: the required forward and
backward operations of every stage and the probe, times fits per second
of the traced window, over the bfloat16 peak."""
import flops


def read(ctx):
    tr = ctx["trace"]
    need = flops.protocol_fit_flops(ctx["config"])
    ops = ctx["traffic"]["seed_lanes_per_fit"] * sum(
        need[s] for s in ("g1_active", "g1_passive", "g2", "g3", "probe"))
    rate = ctx["window"]["fits"] / tr.window_s()
    return 100.0 * ops * rate / ctx["peaks"]["flops_per_s"]
