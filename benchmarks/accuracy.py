"""Paper Fig. 5 / Table 2 accuracy benchmarks on the synthetic datasets,
run through the declarative experiment API (one ExperimentSpec per grid,
``repro.experiments.sweep`` executes it).

Default is --quick (one dataset, two scenarios) so ``benchmarks.run`` stays
CPU-tractable; the full 48-scenario sweep is ``--full`` (hours on 1 core).
"""
from __future__ import annotations

import argparse
import time

from repro.data.synthetic import ALIGNED_SCENARIOS, PAPER_METRIC
from repro.experiments import ExperimentSpec, MethodSpec, sweep
from repro.launch.compile_cache import use_compile_cache


def _by_cell(results):
    """Group a sweep's results back into (n_aligned -> {label: RunResult})."""
    cells: dict = {}
    for r in results:
        cells.setdefault(r.scenario["n_aligned"], {})[r.method] = r
    return cells


def bench_scenarios(dataset: str, aligns, feats, max_epochs: int,
                    seed: int = 0, csv=True):
    """Fig. 5 grid: local / ablation / apcvfl / vfedtrans per (aligned, a).

    One single-cell spec per (aligned, a) so each CSV row reports its OWN
    measured wall time (large n_aligned cells are genuinely slower);
    within a cell the sweep still shares one built scenario across all
    methods."""
    metric = PAPER_METRIC[dataset]
    rows = []
    for a in feats:
        for n_al in aligns:
            spec = ExperimentSpec(
                name=f"accuracy/{dataset}/al{n_al}/a{a}", dataset=dataset,
                aligned=(n_al,), n_active_features=a, seeds=(seed,),
                methods=(MethodSpec("local"),
                         MethodSpec("apcvfl", label="ablation",
                                    params={"ablation": True}),
                         MethodSpec("apcvfl"),
                         MethodSpec("vfedtrans")),
                overrides={"max_epochs": max_epochs})
            t0 = time.time()
            (by,) = _by_cell(sweep(spec)).values()
            us = (time.time() - t0) * 1e6
            r, vt = by["apcvfl"], by["vfedtrans"]
            derived = (f"local={by['local'].metrics[metric]:.4f}|"
                       f"ablation={by['ablation'].metrics[metric]:.4f}|"
                       f"apcvfl={r.metrics[metric]:.4f}|"
                       f"vfedtrans={vt.metrics[metric]:.4f}|"
                       f"apcvfl_MB={r.comm['total_mb']:.2f}|"
                       f"vfedtrans_MB={vt.comm['total_mb']:.2f}")
            if csv:
                print(f"{spec.name},{us:.0f},{derived}", flush=True)
            rows.append({"name": spec.name, "metric": metric,
                         "local": by["local"].metrics[metric],
                         "ablation": by["ablation"].metrics[metric],
                         "apcvfl": r.metrics[metric],
                         "vfedtrans": vt.metrics[metric],
                         "apcvfl_MB": r.comm["total_mb"],
                         "vfedtrans_MB": vt.comm["total_mb"]})
    return rows


def bench_splitnn(dataset: str, aligns, max_epochs: int, seed=0, csv=True):
    """Table 2: classical fully-aligned comparison (one single-cell spec
    per alignment level, so each row's wall time is its own)."""
    metric = PAPER_METRIC[dataset]
    test_size = 50 if dataset == "bcw" else 500
    rows = []
    for n_al in aligns:
        spec = ExperimentSpec(
            name=f"table2/{dataset}/al{n_al}", dataset=dataset,
            aligned=(n_al,), n_active_features=5, seeds=(seed,),
            methods=(MethodSpec("splitnn", params={"test_size": test_size}),
                     MethodSpec("apcvfl_aligned_only",
                                params={"test_size": test_size})),
            overrides={"max_epochs": max_epochs})
        t0 = time.time()
        (by,) = _by_cell(sweep(spec)).values()
        us = (time.time() - t0) * 1e6
        sn, apc = by["splitnn"], by["apcvfl_aligned_only"]
        derived = (f"splitnn={sn.metrics[metric]:.4f}|"
                   f"apcvfl={apc.metrics[metric]:.4f}|"
                   f"splitnn_rounds={sn.rounds}|apcvfl_rounds={apc.rounds}|"
                   f"splitnn_MB={sn.comm['by_stage']['train']/2**20:.2f}|"
                   f"apcvfl_MB={apc.comm['total_mb']:.2f}")
        if csv:
            print(f"{spec.name},{us:.0f},{derived}", flush=True)
        rows.append({"name": spec.name, "splitnn": sn.metrics[metric],
                     "apcvfl": apc.metrics[metric],
                     "splitnn_rounds": sn.rounds,
                     "splitnn_MB": sn.comm["by_stage"]["train"] / 2**20})
    return rows


def run(quick=True, max_epochs=40, csv=True):
    rows = []
    if quick:
        rows += bench_scenarios("bcw", [250, 100], [5, 2], max_epochs, csv=csv)
        rows += bench_splitnn("bcw", [250, 100], max_epochs, csv=csv)
    else:
        for dsname in ["mimic3", "bcw", "credit"]:
            rows += bench_scenarios(dsname, ALIGNED_SCENARIOS[dsname],
                                    [5, 4, 3, 2], max_epochs, csv=csv)
            rows += bench_splitnn(dsname, ALIGNED_SCENARIOS[dsname],
                                  max_epochs, csv=csv)
    return rows


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--max-epochs", type=int, default=40)
    args = ap.parse_args()
    run(quick=not args.full, max_epochs=args.max_epochs)
