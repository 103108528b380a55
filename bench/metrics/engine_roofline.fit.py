"""Share of the lane engine's roofline in a protocol fit: the least time
the chip needs for the engine's required operations and bytes (the larger
of operations over the peak rate and bytes over HBM bandwidth,
``bench/flops.py``) over the device time of ``run_fit_k``."""
import sys

import flops

PROGRAM = "run_fit_k"


def read(ctx):
    tr, fits = ctx["trace"], ctx["window"]["fits"]
    dev = ctx["devices"][0]
    engine_s = tr.module_s(dev, PROGRAM) / fits
    if engine_s <= 0:
        return None
    need = flops.protocol_fit_flops(ctx["config"])
    lanes = ctx["traffic"]["seed_lanes_per_fit"]
    ops = lanes * sum(need[s] for s in ("g1_active", "g1_passive", "g2",
                                        "g3"))
    t_ops = ops / ctx["peaks"]["flops_per_s"]
    t_bytes = lanes * need["engine_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    print(f"engine_roofline.fit: {'compute' if t_ops >= t_bytes else 'memory'}"
          f"-bound, least time {max(t_ops, t_bytes):.6g} s per fit",
          file=sys.stderr)
    return 100.0 * max(t_ops, t_bytes) / engine_s
