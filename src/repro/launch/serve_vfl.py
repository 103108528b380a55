"""Serve a trained APC-VFL model: train -> export -> round-trip through the
checkpoint layer -> drive a simulated request stream through the batched
serving engine (``repro.serve.vfl``).

Run:  PYTHONPATH=src python -m repro.launch.serve_vfl --smoke
      PYTHONPATH=src python -m repro.launch.serve_vfl --dataset bcw \
          --aligned 150 --epochs 30 --requests 5000 --bundle /tmp/apcvfl
      PYTHONPATH=src python -m repro.launch.serve_vfl --load /tmp/apcvfl \
          --requests 1000
      PYTHONPATH=src python -m repro.launch.serve_vfl --load /tmp/apcvfl \
          --arrival poisson --rate-rps 300 --slo-ms 100

With ``--bundle`` the exported ``ModelBundle`` is SAVED to that path and
reloaded before serving, so every run with it proves the save -> load ->
identical-predictions round trip; ``--load`` skips training entirely and
serves an existing bundle (the dataset/scenario is rebuilt only to source
request features).

``--arrival poisson|bursty`` switches from the backlog-drain
``serve_stream`` to the live serving runtime (``repro.serve.runtime``):
requests arrive on a seeded virtual clock, the SLO-aware scheduler
micro-batches them with admission control, and queueing latency is
reported separately from service latency plus SLO attainment and shed
rate.  The multi-tenant version of this loop is
``benchmarks/loadbench.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

import numpy as np

from repro.core import multiparty, pipeline
from repro.data.synthetic import make_dataset
from repro.data.vertical import make_scenario
from repro.launch.compile_cache import use_compile_cache
from repro.serve import vfl as sv


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(
        description="online serving for a trained APC-VFL model")
    ap.add_argument("--dataset", default="bcw")
    ap.add_argument("--aligned", type=int, default=150)
    ap.add_argument("--n-parties", type=int, default=2)
    ap.add_argument("--active-features", type=int, default=5)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--max-rows", type=int, default=64,
                    help="largest request size in the simulated stream")
    ap.add_argument("--p-known", type=float, default=0.5,
                    help="probability a request row keeps its real id "
                         "(cache candidate)")
    ap.add_argument("--buckets", default="16,32,64,128,256")
    ap.add_argument("--quantize", choices=["none", "int8"], default="none",
                    help="'int8' serves the active path from per-channel "
                         "symmetric int8 weights (serve.quant) and prints "
                         "the pinned fp32-parity report")
    ap.add_argument("--arrival", choices=["stream", "poisson", "bursty"],
                    default="stream",
                    help="'stream' = drain the request list as a backlog "
                         "(serve_stream); 'poisson'/'bursty' = live "
                         "arrival-clocked runtime with SLO micro-batching")
    ap.add_argument("--rate-rps", type=float, default=200.0,
                    help="arrival rate for --arrival poisson/bursty")
    ap.add_argument("--slo-ms", type=float, default=100.0,
                    help="end-to-end latency SLO for the live runtime")
    ap.add_argument("--queue-rows", type=int, default=4096,
                    help="admission bound: queued rows beyond this shed")
    ap.add_argument("--fault", default=None, metavar="PLAN.json",
                    help="inject a robustness.faults.FaultPlan into the "
                         "live runtime (requires --arrival poisson|"
                         "bursty); events target tenant 'default' — see "
                         "examples/faults/passive_dropout.json")
    ap.add_argument("--bundle", default=None,
                    help="save the exported bundle here and serve the "
                         "RELOADED copy (round-trip proof)")
    ap.add_argument("--load", default=None,
                    help="serve an existing bundle instead of training")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny settings: 2 epochs, 300 requests")
    ap.add_argument("--out", default=None,
                    help="also write the stream stats JSON here")
    args = ap.parse_args(argv)
    if args.smoke:
        args.epochs = min(args.epochs, 2)
        args.requests = min(args.requests, 300)
    plan = None
    if args.fault:
        if args.arrival == "stream":
            ap.error("--fault needs the live runtime: use --arrival "
                     "poisson or bursty (the backlog drain has no clock "
                     "to trigger events on)")
        from repro.robustness.faults import FaultPlan
        plan = FaultPlan.load(args.fault)
        print(f"fault plan {plan.name!r}: "
              f"{len(plan.serving_events())} serving events")

    ds = make_dataset(args.dataset, seed=args.seed)
    if args.n_parties == 2:
        sc = make_scenario(ds, n_active_features=args.active_features,
                           n_aligned=args.aligned, seed=args.seed)
    else:
        sc = multiparty.make_scenario_k(
            ds, n_parties=args.n_parties,
            n_active_features=args.active_features,
            n_aligned=args.aligned, seed=args.seed)

    if args.load:
        bundle = sv.ModelBundle.load(args.load)
        print(f"loaded bundle {args.load}: {bundle.meta}")
        # the scenario here only sources request features/ids — refuse a
        # bundle trained on a different feature split or dataset before
        # the mismatch surfaces as an XLA shape error (or, worse, silent
        # mis-keyed cache routing)
        d = sc.active.x.shape[1]
        want_d = bundle.meta.get("n_features_active")
        if want_d is not None and int(want_d) != d:
            ap.error(f"bundle expects {want_d} active features but the "
                     f"rebuilt scenario has {d}; rerun with the training "
                     f"flags (--dataset/--active-features/--seed)")
        want_ds = bundle.meta.get("dataset")
        if want_ds and want_ds != args.dataset:
            ap.error(f"bundle was trained on dataset {want_ds!r}, not "
                     f"{args.dataset!r}")
    else:
        print(f"training apcvfl on {args.dataset} "
              f"(K={args.n_parties}, aligned={args.aligned}, "
              f"epochs<={args.epochs}) ...")
        if args.n_parties == 2:
            result = pipeline.run_apcvfl(sc, seed=args.seed,
                                         max_epochs=args.epochs)
        else:
            result = multiparty.run_apcvfl_k(sc, seed=args.seed,
                                             max_epochs=args.epochs)
        print(f"trained: acc={result.metrics['accuracy']:.4f} "
              f"epochs={result.epochs}")
        bundle = sv.export_bundle(result, sc)
        with tempfile.TemporaryDirectory() as tmp:
            path = args.bundle or os.path.join(tmp, "bundle")
            bundle.save(path)
            reloaded = sv.ModelBundle.load(path)   # eager: outlives tmp
            probe = np.asarray(sc.active.x[:32], np.float32)
            a = sv.VFLServingEngine(bundle).predict_active(probe)
            b = sv.VFLServingEngine(reloaded).predict_active(probe)
            assert np.array_equal(a, b), \
                "bundle round-trip changed predictions"
        where = f"{args.bundle}.npz" if args.bundle else "(ephemeral)"
        print(f"bundle saved -> {where} (round-trip verified, "
              f"{bundle.meta['n_cached']} cached latents)")
        bundle = reloaded

    buckets = [int(b) for b in args.buckets.split(",") if b]
    quantize = None if args.quantize == "none" else args.quantize
    if quantize:
        from repro.serve import quant
        parity = quant.parity_report(bundle, sc.active.x, sc.active.y,
                                     n_classes=sc.n_classes)
        print(f"int8 parity vs fp32: max|dlogit|="
              f"{parity['max_abs_logit_delta']:.4f} "
              f"(rel {parity['rel_logit_delta']:.4f}), flip rate "
              f"{parity['pred_flip_rate']:.4f}, "
              f"{parity['compression']}x weight compression")
    if args.arrival != "stream":
        from repro.serve import runtime as rt
        registry = rt.TenantRegistry(buckets=buckets)
        engine = registry.register("default", bundle, quantize=quantize)
        engine.warmup()
        stream = rt.make_timed_stream(
            sc.active.x, sc.active.ids, args.requests,
            tenant="default", arrivals=args.arrival,
            rate_rps=args.rate_rps, seed=args.seed + 1,
            max_rows=args.max_rows, p_known=args.p_known)
        runtime = rt.ServingRuntime(
            registry, rt.RuntimeConfig(slo_ms=args.slo_ms,
                                       max_queue_rows=args.queue_rows))
        stats = runtime.run(stream, faults=plan)
        lat = stats["latency_ms"]
        print(f"\n=== {args.arrival} arrivals at {args.rate_rps} req/s: "
              f"served {stats['served']}/{stats['requests']} requests "
              f"({stats['rows']} rows) in "
              f"{stats['virtual_elapsed_ms']:.0f} virtual ms ===")
        print(f"throughput: {stats['rows_per_s']} rows/s over "
              f"{stats['dispatches']} micro-batches "
              f"(mean {stats['mean_batch_rows']} rows)")
        print(f"queueing  p50/p99: {lat['queue']['p50']} / "
              f"{lat['queue']['p99']} ms")
        print(f"service   p50/p99: {lat['service']['p50']} / "
              f"{lat['service']['p99']} ms")
        print(f"SLO {args.slo_ms} ms: attainment "
              f"{stats['slo']['attainment']}  shed rate "
              f"{stats['shed_rate']}")
        print(f"compiled batch shapes: {stats['compiled']['by_path']} "
              f"(distinct: {stats['compiled']['distinct_batch_shapes']})")
        if plan is not None:
            fb = stats["faults"]["tenants"].get("default", {})
            print(f"faults: applied {stats['faults']['events_applied']} "
                  f"events, faulted={fb.get('faulted')}, "
                  f"collab_while_faulted="
                  f"{fb.get('collab_dispatches_while_faulted')}, "
                  f"cache_stale={fb.get('cache_stale')}, "
                  f"cache_version={fb.get('cache_version')}")
    else:
        engine = sv.VFLServingEngine(bundle, buckets=buckets,
                                     quantize=quantize)
        requests = sv.make_request_stream(
            sc.active.x, sc.active.ids, args.requests, seed=args.seed + 1,
            max_rows=args.max_rows, p_known=args.p_known)
        stats = sv.serve_stream(engine, requests)

        print(f"\n=== served {stats['requests']} requests "
              f"({stats['rows']} rows) in {stats['wall_s']}s ===")
        print(f"throughput: {stats['rows_per_s']} rows/s "
              f"({stats['requests_per_s']} req/s)")
        print(f"latency p50/p99: {stats['latency_ms_p50']} / "
              f"{stats['latency_ms_p99']} ms (service; queueing separate "
              f"in latency_ms block)")
        print(f"cache hit-rate: {stats['cache_hit_rate']}  "
              f"dispatches: {stats['dispatches']}")
        print(f"compiled batch shapes: {stats['compiled']['by_path']} "
              f"(distinct: {stats['compiled']['distinct_batch_shapes']})")
    if quantize:
        stats["quant"] = parity
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(stats, fh, indent=1)
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
