"""The lane-sharded fit's share of the chips' peak: required operations
per fit (every lane's steps and validation passes) times fits per second
of the traced window, over the number of chips times the bfloat16
peak."""
import flops


def read(ctx):
    cfg, tr = ctx["config"], ctx["trace"]
    lanes = cfg["parties"] * cfg["seed_replicas"]
    ops = lanes * flops.stage_fit_flops(
        [cfg["features"], *cfg["encoder"]], cfg["rows"],
        batch_size=cfg["batch_size"], epochs=cfg["max_epochs"])
    rate = ctx["window"]["fits"] / tr.window_s()
    chips = len(ctx["devices"])
    return 100.0 * ops * rate / (chips * ctx["peaks"]["flops_per_s"])
