"""Run a declarative ``ExperimentSpec`` end-to-end from the command line.

Reads a spec JSON file (see ``repro.experiments.specs``), expands the
aligned x K x seed grid, runs every registered method on every cell, and
writes ``results.json`` with the spec echo plus one tidy record per run.

Run:  PYTHONPATH=src python -m repro.launch.experiment SPEC.json \
          [--out results.json]
      PYTHONPATH=src python -m repro.launch.experiment --smoke

``--smoke`` runs a tiny built-in spec (bcw, 120 aligned rows, 2 epochs,
all five methods) — the CI canary for the public entry point.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.experiments import ExperimentSpec, MethodSpec, sweep, tidy
from repro.launch.compile_cache import use_compile_cache


def smoke_spec() -> ExperimentSpec:
    """Tiny spec proving every built-in method runs through one sweep()."""
    return ExperimentSpec(
        name="smoke",
        dataset="bcw",
        aligned=(120,),
        seeds=(0,),
        methods=(MethodSpec("local"),
                 MethodSpec("apcvfl"),
                 MethodSpec("apcvfl", label="ablation",
                            params={"ablation": True}),
                 MethodSpec("splitnn", params={"test_size": 40}),
                 MethodSpec("vfedtrans"),
                 MethodSpec("apcvfl_aligned_only",
                            params={"test_size": 40})),
        overrides={"max_epochs": 2},
    )


def _summary_table(records: list) -> str:
    cols = ["method", "dataset", "n_aligned", "n_parties", "seed",
            "accuracy", "f1_macro", "rounds", "comm_mb"]
    lines = [" ".join(f"{c:>12}" for c in cols)]
    for r in records:
        cells = []
        for c in cols:
            v = r.get(c)
            cells.append(f"{v:>12.4f}" if isinstance(v, float)
                         else f"{str(v):>12}")
        lines.append(" ".join(cells))
    return "\n".join(lines)


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(
        description="run a declarative ExperimentSpec end-to-end")
    ap.add_argument("spec", nargs="?", default=None,
                    help="path to an ExperimentSpec JSON file")
    ap.add_argument("--smoke", action="store_true",
                    help="run the tiny built-in smoke spec instead")
    ap.add_argument("--out", default="results.json",
                    help="output path (default: results.json)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-run progress lines")
    args = ap.parse_args(argv)

    if args.smoke == (args.spec is not None):
        ap.error("give exactly one of SPEC.json or --smoke")
    if args.smoke:
        spec = smoke_spec()
    else:
        with open(args.spec) as fh:
            spec = ExperimentSpec.from_json(fh.read())

    t0 = time.time()
    results = sweep(spec, progress=None if args.quiet else print)
    records = tidy(results)
    payload = {"spec": spec.to_dict(), "records": records,
               "elapsed_s": round(time.time() - t0, 1)}
    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"\n=== {spec.name}: {len(records)} runs in "
          f"{payload['elapsed_s']}s -> {args.out} ===")
    print(_summary_table(records))
    return 0


if __name__ == "__main__":
    sys.exit(main())
