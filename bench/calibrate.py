#!/usr/bin/env python3
"""Readings for the limits of ``correct``: runs one cell on many seeds in
one process (the program's readings), then the control (the reference in
bfloat16 in the program's place) on the last seeds, and prints each run's
numbers as JSON lines.  Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 4,5,6] [--seconds 0.1] \\
        [--fault name,name] [--precision highest]

``--fault`` runs the ``--seeds`` once under each fault named
(``bench/faults.py``) in place of the sound program; ``--precision``
runs the program with JAX's default matmul precision set to that value.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.1)
    ap.add_argument("--fault", default="",
                    help="comma-separated faults (bench/faults.py), each "
                         "planted in the program for the --seeds runs")
    ap.add_argument("--precision", default="",
                    help="JAX default matmul precision for the program")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    devices = run.start_jax(spec["cell"]["chips"])
    import jax

    import faults
    if args.precision:
        jax.config.update("jax_default_matmul_precision", args.precision)
    plan = [(f or "program", args.seeds) for f in args.fault.split(",")]
    plan.append(("control", args.control_seeds))
    for kind, seeds in plan:
        for s in [int(v) for v in seeds.split(",") if v]:
            t = time.perf_counter()
            fault = kind if kind not in ("program", "control") else ""
            with (faults.plant(fault) if fault
                  else contextlib.nullcontext()):
                out = run.run_cell(args.workload, s, args.seconds, False,
                                   devices=devices,
                                   control=kind == "control", t_start=t)
            print(json.dumps({"kind": kind, "seed": s,
                              "precision": args.precision or "default",
                              "readings": out["readings"],
                              "metrics": out["metrics"],
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
