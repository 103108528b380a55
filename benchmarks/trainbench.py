"""Training-engine and experiment-harness throughput.

Default mode measures the device-resident scan engine
(``training.train``) on the paper's g1-sized autoencoder workload
(Table 3 g1_active: D -> 64 -> 128, symmetric decoder) across batch
sizes.  The retired per-batch host loop measured 6.5x slower at bs=32
and ~2.5x at bs=128 on a 2-core CPU container (PR 1); the engine's
semantics are now pinned by the stored-trace oracle
(``tests/data/train_trace.json``) instead of a live parity run.

K-party mode (``--kparty``) benchmarks the lane engine on K parties
(``training.train_lanes``: all K parties' g1 stages as lanes of ONE
vmapped scan — one dispatch + one host sync per fit) against K sequential
``training.train`` calls (each a lane of one: K dispatches, K syncs), for
K in {2, 4, 8} with uneven per-party feature widths (exercising the
padded-stack layout).

Sweep mode (``--sweep``) benchmarks the replica-lane sweep engine: one
grid cell x S seed replicas of the full APC-VFL protocol, replicated
(every stage S stacked lanes of one vmapped scan, via
``run_apcvfl_replicated``) vs sequential (S independent protocol runs),
plus the per-method wall time of the smoke spec.  Writes a
machine-readable ``BENCH_sweep.json`` (wall-clock per path, engine
steps/s, per-stage lane occupancy) so the perf trajectory accrues across
PRs; CI uploads it as an artifact.

Scale mode (``--scale``) is the million-row device-count sweep: a
10^6-row x 8-party x multi-seed synthetic vertical partition
(``data.scale.make_scale_lanes``, built device-resident) trained through
the mesh-sharded fused lane engine (``train_lanes(..., mesh=...)``) at
increasing device counts.  All counts run in this one process, each on a
mesh over the first n of ``jax.devices()``; the scaling curve, with the
device it ran on, goes to ``BENCH_scale.json``.  On the CPU the fake
host device count (``--xla_force_host_platform_device_count``) is set
once, before JAX starts, to the largest count; those fake devices share
cores, so the curve shows the sharding mechanism and its overhead, not a
speedup.  ``--smoke`` shrinks the grid for CI.

Run:  PYTHONPATH=src python benchmarks/trainbench.py [--rows 4096]
      [--features 30] [--epochs 20] [--batches 32,64,128] [--csv]
      [--kparty] [--ks 2,4,8] [--sweep] [--seeds 5]
      [--out BENCH_sweep.json]
      [--scale [--smoke] [--devices-list 1,2,4,8] [--parties 8]
       [--scale-seeds 2] [--scale-bs 8192] [--dp 1]
       [--scale-out BENCH_scale.json]]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core import autoencoder as ae
from repro.core import training
from repro.launch.compile_cache import use_compile_cache


def _steps_per_sec(params, data, *, batch_size, epochs) -> float:
    kw = dict(batch_size=batch_size, max_epochs=epochs, patience=epochs,
              seed=0)
    training.train(params, data, ae.recon_loss,
                   **dict(kw, max_epochs=2))                       # warm
    t0 = time.time()
    r = training.train(params, data, ae.recon_loss, **kw)
    return r.steps_run / (time.time() - t0)


def run(rows: int = 4096, features: int = 30, epochs: int = 20,
        batch_sizes=(32, 64, 128), csv: bool = True) -> list:
    x = np.random.RandomState(0).randn(rows, features).astype(np.float32)
    params = ae.init_autoencoder(jax.random.PRNGKey(0),
                                 ae.table3_encoder("g1_active", features))
    rows_out = []
    for bs in batch_sizes:
        scan = _steps_per_sec(params, {"x": x}, batch_size=bs, epochs=epochs)
        rec = {"name": f"trainbench/g1/n{rows}/bs{bs}",
               "scan_steps_per_s": scan}
        rows_out.append(rec)
        if csv:
            print(f"{rec['name']},{1e6 / scan:.0f},scan={scan:.0f}sps",
                  flush=True)
    return rows_out


def _kparty_specs(k: int, rows: int, features: int):
    """K parties with uneven feature widths (features, features+1, ...)."""
    specs = []
    for i in range(k):
        d = features + i
        x = np.random.RandomState(i).randn(rows, d).astype(np.float32)
        params = ae.init_autoencoder(jax.random.PRNGKey(i),
                                     ae.table3_encoder("g1_passive", d))
        specs.append(training.LaneSpec(params, {"x": x}, seed=i))
    return specs


def run_kparty(rows: int = 2048, features: int = 24, epochs: int = 10,
               batch_size: int = 32, ks=(2, 4, 8), csv: bool = True) -> list:
    """train_lanes (one vmapped scan for all K parties) vs K sequential
    training.train calls, total steps/s across parties."""
    rows_out = []
    for k in ks:
        specs = _kparty_specs(k, rows, features)
        kw = dict(batch_size=batch_size, max_epochs=epochs, patience=epochs)

        def seq():
            return [training.train(s.params, s.data, ae.recon_loss,
                                   seed=s.seed, **kw) for s in specs]

        def batched():
            return training.train_lanes(specs, ae.masked_recon_loss, **kw)

        for fn in (seq, batched):          # warm both compile caches
            fn()
        t0 = time.time()
        r_seq = seq()
        t_seq = time.time() - t0
        t0 = time.time()
        r_bat = batched()
        t_bat = time.time() - t0
        steps = sum(r.steps_run for r in r_seq)
        assert steps == sum(r.steps_run for r in r_bat)
        rec = {"name": f"trainbench/kparty/K{k}/n{rows}/bs{batch_size}",
               "vmapped_steps_per_s": steps / t_bat,
               "sequential_steps_per_s": steps / t_seq,
               "speedup": t_seq / t_bat}
        rows_out.append(rec)
        if csv:
            print(f"{rec['name']},{1e6 * t_bat / steps:.0f},"
                  f"vmapped={rec['vmapped_steps_per_s']:.0f}sps|"
                  f"sequential={rec['sequential_steps_per_s']:.0f}sps|"
                  f"speedup={rec['speedup']:.1f}x", flush=True)
    return rows_out


def _cell_steps(epochs: dict, stage_rows: dict, bs: int) -> int:
    """Total engine steps one protocol run took, reconstructed from its
    per-stage epoch counts and the engine's batching contract
    (``n_batches = n_tr // bs`` after the shared lane-group clamp;
    identical for the sequential and the replica-lane path at equal
    shapes).  ``stage_rows``: stage -> (rows, lane_group) where stages in
    one group share the batch-size clamp."""
    def n_tr(n):
        return n - max(int(n * 0.1), 1)

    groups: dict = {}
    for n, g in stage_rows.values():
        groups.setdefault(g, []).append(n_tr(n))
    bs_g = {g: max(min([bs] + v), 1) for g, v in groups.items()}
    return sum(epochs.get(st, 0) * (n_tr(n) // bs_g[g])
               for st, (n, g) in stage_rows.items())


def _stage_rows(method: str, scenario) -> dict:
    n_a, n_p = len(scenario.active.x), len(scenario.passive.x)
    n_al = scenario.n_aligned
    if method == "apcvfl":
        return {"g1_active": (n_a, "g1"), "g1_passive": (n_p, "g1"),
                "g2": (n_al, "g2"), "g3": (n_a, "g3")}
    return {"g1_active": (n_al, "g1"), "g1_passive": (n_al, "g1"),
            "g2": (n_al, "g2")}              # aligned-only variant


def _lane_occupancy(results) -> dict:
    """Per-stage lane occupancy of a replica group: mean over lanes of
    (own epochs / slowest lane's epochs) — 1.0 means no lane idled behind
    a slower sibling, lower means early-stopped lanes spent epochs
    frozen-stepping."""
    out = {}
    for stage, lanes in (("g1", ["g1_active", "g1_passive"]),
                         ("g2", ["g2"]), ("g3", ["g3"])):
        eps = [r.epochs[k] for r in results for k in lanes
               if k in r.epochs]
        if eps:
            out[stage] = float(np.mean(eps) / max(eps))
    return out


# PR 5's recorded sweep speedups (replicated / sequential wall) on the
# 2-core CI container — the yardstick each re-run reports its delta
# against.  On a SINGLE-core host the ratio's ceiling is ~1.06: lane
# batching removes dispatch overhead but the lanes' arithmetic still
# shares one core, so a lower ratio there is expected, not a regression
# (BENCH_scale.json demonstrates the same engine scaling with real
# device counts).
_SWEEP_BASELINE_SPEEDUP = {"apcvfl": 0.82, "apcvfl_aligned_only": 1.14}


def _median_wall(fn, repeats: int):
    """Median warm wall-clock of ``fn`` over ``repeats`` runs, plus the
    LAST run's result and the total compile count (snapshotted — the
    tally is a live property)."""
    from repro.analysis import guards

    walls, res = [], None
    with guards.compile_counter() as tally:
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = fn()
            walls.append(time.perf_counter() - t0)
    compiles = tally.count
    return float(np.median(walls)), res, compiles


def run_sweep(epochs: int = 30, seeds: int = 5, repeats: int = 3,
              out_json="BENCH_sweep.json", csv: bool = True) -> dict:
    """Replica-lane sweep engine vs sequential per-seed execution: one
    grid cell x ``seeds`` replicas for each method with a replicated
    runner (full apcvfl protocol + the aligned-only adaptation), plus the
    smoke spec's per-method wall times; writes ``out_json``.

    Methodology: both paths are compile-warmed first, then timed
    ``repeats`` times each and the MEDIAN wall is reported (single warm
    runs on a shared CPU container jitter by 10-20%, which used to
    swallow the effect being measured).  Each record carries the PR 5
    baseline speedup and this run's delta against it, plus a machine
    note — on a 1-core host the ratio is dispatch-overhead-only (see
    ``_SWEEP_BASELINE_SPEEDUP``).

    ``bs=32`` keeps the stages in the dispatch-bound regime the lane
    engine targets (PR 2's K-party setting).  Expect the aligned-only
    grid to show the larger win: both of its stages (g1, g2) batch well,
    while full apcvfl is diluted by the compute-bound g3 and the
    memory-bound k-fold probe, which lane-batching cannot speed up on
    CPU."""
    from dataclasses import replace

    from repro.experiments import (ExperimentSpec, MethodSpec,
                                   build_scenario, get_method, sweep)
    from repro.launch.experiment import smoke_spec

    try:
        n_cpu = len(os.sched_getaffinity(0))
    except AttributeError:          # non-linux fallback
        n_cpu = os.cpu_count() or 1

    # --- replicated vs sequential, per replicable method ------------------
    bs = 32
    replicas = {}
    grids = (MethodSpec("apcvfl"),
             MethodSpec("apcvfl_aligned_only", params={"test_size": 40}))
    for m in grids:
        spec = ExperimentSpec(
            name=f"bench-{m.method}", dataset="bcw", aligned=(150,),
            seeds=tuple(range(seeds)), methods=(m,),
            overrides={"max_epochs": epochs, "patience": epochs,
                       "batch_size": bs})
        seq_spec = replace(spec, replicate=False)
        for s in (seq_spec, spec):        # warm both compile caches
            sweep(s)
        t_seq, seq_res, seq_compiles = _median_wall(
            lambda: sweep(seq_spec), repeats)
        t_rep, rep_res, rep_compiles = _median_wall(
            lambda: sweep(spec), repeats)

        cell = build_scenario(next(iter(spec.scenarios())))
        steps = sum(_cell_steps(r.epochs, _stage_rows(m.method, cell), bs)
                    for r in seq_res)
        baseline = _SWEEP_BASELINE_SPEEDUP.get(m.method)
        speedup = round(t_seq / t_rep, 3)
        bench = {
            "name": f"trainbench/sweep/{m.method}/S{seeds}/e{epochs}",
            "grid": {"dataset": "bcw", "aligned": 150, "seeds": seeds,
                     "method": m.method, "max_epochs": epochs,
                     "batch_size": bs},
            "total_steps": steps,
            "repeats": repeats,
            "sequential_wall_s": round(t_seq, 3),
            "replicated_wall_s": round(t_rep, 3),
            "speedup": speedup,
            "baseline_speedup": baseline,
            "speedup_delta_vs_baseline":
                round(speedup - baseline, 3) if baseline else None,
            "cpus_visible": n_cpu,
            "machine_note": (
                "medians of warm repeats; on a 1-core host the "
                "replicated/sequential ratio measures dispatch overhead "
                "only (ceiling ~1.06) — the PR 5 baseline was a 2-core "
                "container" if n_cpu <= 1 else
                "medians of warm repeats on a multi-core host"),
            "sequential_steps_per_s": round(steps / t_seq, 1),
            "replicated_steps_per_s": round(steps / t_rep, 1),
            "lane_occupancy": _lane_occupancy(rep_res),
            # warmed runs: compile stability proof (0 = jit caches held)
            "xla_compiles_warm_sequential": seq_compiles,
            "xla_compiles_warm_replicated": rep_compiles,
        }
        replicas[m.method] = bench
        if csv:
            print(f"{bench['name']},{1e6 * t_rep / max(steps, 1):.0f},"
                  f"replicated={bench['replicated_steps_per_s']:.0f}sps|"
                  f"sequential={bench['sequential_steps_per_s']:.0f}sps|"
                  f"speedup={bench['speedup']:.2f}x|"
                  f"baseline={baseline}x", flush=True)

    # --- per-method wall time of one smoke-spec cell ----------------------
    mspec_all = replace(smoke_spec(), overrides={"max_epochs": epochs})
    sweep(mspec_all)              # validate + warm remaining compiles
    scenario = build_scenario(next(iter(mspec_all.scenarios())))
    seed = mspec_all.seeds[0]
    rows_out = []
    for m in mspec_all.methods:
        mspec = replace(m, params={**mspec_all.overrides, **m.params})
        entry = get_method(m.method)
        t0 = time.time()
        result = entry.fn(scenario, mspec, seed=seed)
        us = (time.time() - t0) * 1e6
        rec = {"name": f"trainbench/sweep/{m.row_label}/e{epochs}",
               "wall_s": us / 1e6, "accuracy": result.metrics["accuracy"]}
        rows_out.append(rec)
        if csv:
            print(f"{rec['name']},{us:.0f},"
                  f"wall={rec['wall_s']:.2f}s|acc={rec['accuracy']:.4f}",
                  flush=True)

    payload = {"replicas": replicas, "per_method": rows_out}
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(payload, fh, indent=1)
        if csv:
            print(f"# wrote {out_json}", flush=True)
    return payload


# ---------------------------------------------------------------------------
# scale mode: million-row device-count sweep (BENCH_scale.json)
# ---------------------------------------------------------------------------

def run_scale_cell(*, devices: int, rows: int, parties: int, seeds: int,
                   features: int, epochs: int, batch_size: int,
                   dp: int) -> dict:
    """One device count: a lane mesh over the first ``devices`` of
    ``jax.devices()``, the lanes generated device-resident on it, all
    party x seed lanes trained through the mesh-sharded fused engine;
    records cold (compile+run) and warm wall clock."""
    from repro.data.scale import make_scale_lanes
    from repro.launch.mesh import make_lane_mesh

    if devices % dp:
        raise ValueError(f"--dp {dp} does not divide {devices} devices")
    mesh = make_lane_mesh(lane=devices // dp, data=dp)
    t0 = time.time()
    lanes = make_scale_lanes(rows, parties, n_features=features,
                             seeds=tuple(range(seeds)), mesh=mesh)
    jax.block_until_ready([sp.data["x"] for sp in lanes])
    gen_s = time.time() - t0

    kw = dict(batch_size=batch_size, max_epochs=epochs, patience=epochs,
              mesh=mesh, shard_rows=dp > 1)
    t0 = time.time()
    results = training.train_lanes(lanes, ae.masked_recon_loss, **kw)
    cold_s = time.time() - t0
    t0 = time.time()
    results = training.train_lanes(lanes, ae.masked_recon_loss, **kw)
    warm_s = time.time() - t0

    steps = int(sum(r.steps_run for r in results))
    return {
        "devices": devices,
        "mesh": dict(mesh.shape),
        "lanes": len(lanes),
        "gen_s": round(gen_s, 3),
        "train_cold_s": round(cold_s, 3),
        "train_warm_s": round(warm_s, 3),
        "steps": steps,
        "steps_per_s_warm": round(steps / warm_s, 2),
        "rows_per_s_warm": round(steps * batch_size / warm_s, 1),
        "final_train_loss": float(np.mean([r.train_loss[-1]
                                           for r in results])),
    }


def run_scale(*, rows: int = 1_000_000, parties: int = 8, seeds: int = 2,
              features: int = 16, epochs: int = 2, batch_size: int = 8192,
              dp: int = 1, device_counts=(1, 2, 4, 8),
              out_json: str = "BENCH_scale.json", csv: bool = True) -> dict:
    """The device-count sweep, in this one process: each count trains on
    a mesh over the first n devices, aggregated into ``out_json``."""
    d0 = jax.devices()[0]
    cells = []
    for n in device_counts:
        cell = run_scale_cell(devices=n, rows=rows, parties=parties,
                              seeds=seeds, features=features, epochs=epochs,
                              batch_size=batch_size, dp=dp)
        cells.append(cell)
        if csv:
            print(f"trainbench/scale/dev{n},"
                  f"{1e6 * cell['train_warm_s'] / max(cell['steps'], 1):.0f},"
                  f"warm={cell['train_warm_s']:.2f}s|"
                  f"cold={cell['train_cold_s']:.2f}s|"
                  f"{cell['rows_per_s_warm']:.0f}rows/s", flush=True)

    base = cells[0]["train_warm_s"]
    payload = {
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": jax.device_count()},
        "grid": {"rows": rows, "parties": parties, "seeds": seeds,
                 "lanes": parties * seeds, "features": features,
                 "epochs": epochs, "batch_size": batch_size, "dp": dp,
                 "device_counts": list(device_counts)},
        "cells": cells,
        "speedup_vs_1dev": {str(c["devices"]): round(base
                                                     / c["train_warm_s"], 3)
                            for c in cells},
    }
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(payload, fh, indent=1)
        if csv:
            print(f"# wrote {out_json}", flush=True)
    return payload


def _fake_cpu_devices(n: int) -> None:
    """Give the CPU backend ``n`` devices unless ``XLA_FLAGS`` already
    sets a count.  Must run before JAX starts its backends; on an
    accelerator the flag only shapes the unused host platform."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--features", type=int, default=30)
    ap.add_argument("--epochs", type=int, default=None,
                    help="training budget (default: 20; 30 for --sweep)")
    ap.add_argument("--batches", default="32,64,128")
    ap.add_argument("--kparty", action="store_true",
                    help="run the K-party train_lanes vs sequential sweep")
    ap.add_argument("--ks", default="2,4,8")
    ap.add_argument("--sweep", action="store_true",
                    help="benchmark the replica-lane sweep engine "
                         "(replicated vs sequential seeds) and the "
                         "per-method harness; writes --out")
    ap.add_argument("--seeds", type=int, default=5,
                    help="seed replicas for the --sweep benchmark")
    ap.add_argument("--out", default="BENCH_sweep.json",
                    help="--sweep JSON output path ('' to skip)")
    ap.add_argument("--scale", action="store_true",
                    help="million-row device-count sweep through the "
                         "mesh-sharded lane engine; writes --scale-out")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the --scale grid for CI")
    ap.add_argument("--devices-list", default="",
                    help="--scale device counts (default 1,2,4,8; "
                         "smoke 1,2)")
    ap.add_argument("--parties", type=int, default=None)
    ap.add_argument("--scale-seeds", type=int, default=2)
    ap.add_argument("--scale-bs", type=int, default=None)
    ap.add_argument("--dp", type=int, default=1,
                    help="row-sharding (data axis) devices per lane group")
    ap.add_argument("--scale-out", default="BENCH_scale.json")
    args = ap.parse_args()
    if args.scale:
        smoke = args.smoke
        devs = ([int(d) for d in args.devices_list.split(",") if d]
                or ([1, 2] if smoke else [1, 2, 4, 8]))
        _fake_cpu_devices(max(devs))
        run_scale(
            rows=args.rows if args.rows != 4096 else
            (16_384 if smoke else 1_000_000),
            parties=args.parties or (4 if smoke else 8),
            seeds=args.scale_seeds,
            features=args.features if args.features != 30 else 16,
            epochs=args.epochs or 2,
            batch_size=args.scale_bs or (512 if smoke else 8192),
            dp=args.dp, device_counts=devs, out_json=args.scale_out)
    elif args.sweep:
        run_sweep(epochs=args.epochs if args.epochs is not None else 30,
                  seeds=args.seeds, out_json=args.out)
    elif args.kparty:
        run_kparty(rows=args.rows, features=args.features,
                   epochs=args.epochs if args.epochs is not None else 20,
                   batch_size=int(args.batches.split(",")[0]),
                   ks=[int(k) for k in args.ks.split(",") if k])
    else:
        run(rows=args.rows, features=args.features,
            epochs=args.epochs if args.epochs is not None else 20,
            batch_sizes=[int(b) for b in args.batches.split(",") if b])


if __name__ == "__main__":
    main()
