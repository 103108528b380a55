"""Multi-tenant load benchmark for the live VFL serving runtime
(``repro.serve.runtime``) — the "millions of users" artifact.

Trains one small APC-VFL model per tenant, registers every exported
``ModelBundle`` behind ONE ``TenantRegistry`` (shared bucketer + shared
jit cache — warming tenant N+1 must cost zero XLA compiles), then drives
three load segments through the SLO-aware micro-batching scheduler:

* **poisson** — steady memoryless traffic per tenant;
* **bursty**  — on/off modulated flash-crowd traffic;
* **overload** — a short burst far past capacity against a small
  admission bound, proving load shedding engages (shed rate > 0) while
  admitted requests still complete;
* **int8** — every tenant re-registered as a quantized twin
  (``serve.quant``) behind the SAME registry: warming the twins must
  cost zero compiles (pre-dequantized params keep the fp32 pytree
  shape), the segment's rows/s must hold >= 0.9x the fp32 poisson
  segment, and each tenant's fp32-vs-int8 parity must sit inside the
  pinned ``serve.quant`` bounds.

Each segment reports queueing latency and service latency as SEPARATE
percentile series (the ``serve.metrics`` schema BENCH_serve.json also
uses), per-tenant rows/s, SLO attainment, and shed rate — and replays
every dispatched micro-batch through a fresh solo ``VFLServingEngine``
per tenant to prove bit-identical parity with dedicated serving.

Writes ``BENCH_load.json`` with the acceptance block gated in CI:
SLO attainment >= the ``load_stream.slo_attainment_min`` budget
(``ANALYSIS_budgets.json``) under Poisson AND bursty arrivals, zero
steady-state XLA compiles (via ``analysis.guards.compile_counter``),
zero incremental compiles registering same-architecture tenants,
bit-identical per-tenant parity, and shedding exercised under overload.

Run:  PYTHONPATH=src python benchmarks/loadbench.py [--smoke]
      [--tenants 3] [--requests 2000] [--rate-rps 400] [--slo-ms 100]
      [--epochs 15] [--out BENCH_load.json]
"""
from __future__ import annotations

import argparse
import json
import time

from repro.analysis import guards
from repro.core import pipeline
from repro.data.synthetic import make_dataset
from repro.data.vertical import make_scenario
from repro.serve import runtime as rt
from repro.serve import vfl as sv
from repro.launch.compile_cache import use_compile_cache


def _segment(registry, bundles, scenarios, *, arrivals: str,
             requests: int, rate_rps: float, slo_ms: float,
             max_queue_rows: int, max_rows: int, seed: int,
             burst: dict | None = None,
             names: list | None = None) -> dict:
    """One load segment: per-tenant timed streams -> merged -> runtime,
    with steady-state compiles counted and dispatch parity replayed.
    ``names`` restricts the segment to a tenant subset (the int8 segment
    drives only the quantized twins)."""
    streams = []
    for k, name in enumerate(names if names is not None
                             else registry.names()):
        sc = scenarios[name]
        streams.append(rt.make_timed_stream(
            sc.active.x, sc.active.ids, requests, tenant=name,
            arrivals=arrivals, rate_rps=rate_rps, burst=burst,
            seed=seed + 101 * k, max_rows=max_rows))
    runtime = rt.ServingRuntime(
        registry, rt.RuntimeConfig(slo_ms=slo_ms,
                                   max_queue_rows=max_queue_rows))
    registry.reset_stats()
    with guards.compile_counter() as steady:
        report = runtime.run(rt.merge_streams(*streams))
    report["xla_compiles_stream"] = steady.count
    report["parity"] = rt.verify_dispatch_parity(runtime, bundles)
    return report


def run(*, tenants: int = 3, requests: int = 2000, rate_rps: float = 400.0,
        slo_ms: float = 100.0, max_rows: int = 24, max_queue_rows: int = 4096,
        epochs: int = 15, aligned: int = 150, seed: int = 0,
        out_json: str = "BENCH_load.json") -> dict:
    if tenants < 3:
        raise ValueError("loadbench is a multi-tenant benchmark: "
                         "--tenants must be >= 3")
    budgets = guards.load_budgets()["load_stream"]

    # --- one trained model per tenant (distinct seeds = distinct params) --
    bundles, scenarios, train_log = {}, {}, []
    t0 = time.time()
    for k in range(tenants):
        name = f"tenant{k}"
        ds = make_dataset("bcw", seed=seed + k)
        sc = make_scenario(ds, n_active_features=5, n_aligned=aligned,
                           seed=seed + k)
        result = pipeline.run_apcvfl(sc, seed=seed + k, max_epochs=epochs)
        bundles[name] = sv.export_bundle(result, sc)
        scenarios[name] = sc
        train_log.append({"tenant": name, "seed": seed + k,
                          "accuracy": result.metrics["accuracy"]})
        print(f"# trained {name} (seed {seed + k}): "
              f"acc={result.metrics['accuracy']:.4f}", flush=True)
    train_s = time.time() - t0

    # --- registry: many bundles, ONE bucketer, ONE jit cache ---------------
    registry = rt.TenantRegistry()
    first = next(iter(bundles))
    registry.register(first, bundles[first])
    with guards.compile_counter() as warm0:
        registry[first].warmup()
    with guards.compile_counter() as warm_rest:
        for name, b in bundles.items():
            if name != first:
                registry.register(name, b)
                registry[name].warmup()
    # snapshot now: CompileTally.count is LIVE (global counter minus
    # start), so reading it after later segments would inflate it
    warm0_compiles = warm0.count
    incr_compiles = warm_rest.count
    print(f"# warmup: {warm0_compiles} compiles for {first}, "
          f"{incr_compiles} incremental for the other "
          f"{tenants - 1} tenants (shared jit cache)", flush=True)

    seg_kw = dict(requests=requests, rate_rps=rate_rps, slo_ms=slo_ms,
                  max_queue_rows=max_queue_rows, max_rows=max_rows,
                  seed=seed + 1)
    segments = {}
    for mode in ("poisson", "bursty"):
        rep = _segment(registry, bundles, scenarios, arrivals=mode,
                       **seg_kw)
        segments[mode] = rep
        lat = rep["latency_ms"]
        print(f"loadbench/{mode}/t{tenants}x{requests},"
              f"rows_per_s={rep['rows_per_s']:.0f}|"
              f"queue_p50={lat['queue']['p50']}ms|"
              f"queue_p99={lat['queue']['p99']}ms|"
              f"service_p50={lat['service']['p50']}ms|"
              f"service_p99={lat['service']['p99']}ms|"
              f"slo={rep['slo']['attainment']}|"
              f"shed={rep['shed_rate']}|"
              f"compiles={rep['xla_compiles_stream']}", flush=True)

    # --- overload: prove admission control sheds instead of melting -------
    overload = _segment(
        registry, bundles, scenarios, arrivals="bursty",
        requests=max(50, requests // 4), rate_rps=rate_rps * 20,
        slo_ms=slo_ms, max_queue_rows=max(registry.bucketer.max, 128),
        max_rows=max_rows, seed=seed + 2,
        burst={"rate_on_rps": rate_rps * 40, "rate_off_rps": rate_rps,
               "on_ms": 100.0, "off_ms": 50.0})
    segments["overload"] = overload
    print(f"loadbench/overload,shed_rate={overload['shed_rate']}|"
          f"served={overload['served']}|"
          f"slo={overload['slo']['attainment']}", flush=True)

    # --- int8: quantized twins behind the SAME registry -------------------
    # each tenant gets an int8 twin (serve.quant); pre-dequantized params
    # keep the fp32 pytree shape, so warming the twins must cost zero
    # compiles — a mixed fp32/int8 fleet shares one jit cache.
    from repro.serve import quant
    int8_names, bundles_int8 = [], {}
    with guards.compile_counter() as warm_int8:
        for name in list(bundles):
            twin = f"{name}-int8"
            registry.register(twin, bundles[name], quantize="int8")
            registry[twin].warmup()
            scenarios[twin] = scenarios[name]
            bundles_int8[twin] = bundles[name]
            int8_names.append(twin)
    int8_warm_compiles = warm_int8.count        # snapshot (live counter)
    int8_seg = _segment(registry, bundles_int8, scenarios,
                        arrivals="poisson", names=int8_names, **seg_kw)
    parity_bounds = {}
    for name in bundles:
        sc = scenarios[name]
        parity_bounds[name] = quant.parity_report(
            bundles[name], sc.active.x, sc.active.y,
            n_classes=sc.n_classes)
    int8_seg["quant_parity"] = parity_bounds
    int8_seg["warm_compiles"] = int8_warm_compiles
    segments["int8"] = int8_seg
    worst_dlogit = max(p["max_abs_logit_delta"]
                       for p in parity_bounds.values())
    worst_f1 = max(p["f1_macro_delta"] for p in parity_bounds.values())
    print(f"loadbench/int8/t{tenants}x{requests},"
          f"rows_per_s={int8_seg['rows_per_s']:.0f}|"
          f"warm_compiles={int8_warm_compiles}|"
          f"max_dlogit={worst_dlogit:.4f}|"
          f"max_f1_delta={worst_f1:.4f}|"
          f"slo={int8_seg['slo']['attainment']}", flush=True)

    parity_ok = all(
        t["bit_identical"]
        for mode in ("poisson", "bursty")
        for t in segments[mode]["parity"].values())
    acceptance = {
        "tenants": tenants,
        "slo_ms": slo_ms,
        "slo_attainment_min": budgets["slo_attainment_min"],
        "slo_attainment_poisson": segments["poisson"]["slo"]["attainment"],
        "slo_attainment_bursty": segments["bursty"]["slo"]["attainment"],
        "slo_ok": all(
            segments[m]["slo"]["attainment"] >= budgets["slo_attainment_min"]
            for m in ("poisson", "bursty")),
        "stream_compiles": [segments[m]["xla_compiles_stream"]
                            for m in ("poisson", "bursty")],
        "stream_compiles_ok": all(
            segments[m]["xla_compiles_stream"] <= budgets["warm_compiles"]
            for m in ("poisson", "bursty")),
        "tenant_incremental_compiles": incr_compiles,
        "shared_jit_ok": incr_compiles == 0,
        "parity_bit_identical": parity_ok,
        "shed_exercised": overload["shed_rate"] > 0.0,
        # int8 twins: zero extra compiles, throughput at parity with the
        # fp32 poisson segment, quantization error inside serve.quant's
        # pinned bounds, dispatch bit-identical to dedicated int8 serving
        "int8_warm_compiles": int8_warm_compiles,
        "int8_shared_jit_ok": int8_warm_compiles == 0,
        "int8_rows_per_s": int8_seg["rows_per_s"],
        "int8_throughput_ratio": round(
            int8_seg["rows_per_s"]
            / max(segments["poisson"]["rows_per_s"], 1e-9), 3),
        "int8_throughput_ok": int8_seg["rows_per_s"]
            >= 0.9 * segments["poisson"]["rows_per_s"],
        "int8_parity_bound_ok": (
            worst_dlogit <= quant.MAX_LOGIT_DELTA
            and worst_f1 <= quant.MAX_F1_DELTA),
        "int8_dispatch_bit_identical": all(
            t["bit_identical"] for t in int8_seg["parity"].values()),
    }
    acceptance["ok"] = all((
        acceptance["slo_ok"], acceptance["stream_compiles_ok"],
        acceptance["shared_jit_ok"], acceptance["parity_bit_identical"],
        acceptance["shed_exercised"], acceptance["int8_shared_jit_ok"],
        acceptance["int8_parity_bound_ok"],
        acceptance["int8_dispatch_bit_identical"]))
    print(f"# acceptance: slo_ok={acceptance['slo_ok']} "
          f"({acceptance['slo_attainment_poisson']}/"
          f"{acceptance['slo_attainment_bursty']} >= "
          f"{budgets['slo_attainment_min']}), "
          f"stream_compiles_ok={acceptance['stream_compiles_ok']}, "
          f"shared_jit_ok={acceptance['shared_jit_ok']}, "
          f"parity={parity_ok}, "
          f"shed_exercised={acceptance['shed_exercised']}, "
          f"int8: shared_jit={acceptance['int8_shared_jit_ok']} "
          f"throughput={acceptance['int8_throughput_ratio']}x "
          f"parity_bound={acceptance['int8_parity_bound_ok']} "
          f"bit_identical={acceptance['int8_dispatch_bit_identical']}",
          flush=True)

    payload = {
        "name": f"loadbench/bcw/t{tenants}/r{requests}/rps{rate_rps:g}",
        "train": {"epochs": epochs, "wall_s": round(train_s, 2),
                  "tenants": train_log},
        "warmup": {"first_tenant_compiles": warm0_compiles,
                   "incremental_tenant_compiles": incr_compiles},
        "config": {"tenants": tenants, "requests_per_tenant": requests,
                   "rate_rps_per_tenant": rate_rps, "slo_ms": slo_ms,
                   "max_rows": max_rows, "max_queue_rows": max_queue_rows,
                   "seed": seed},
        "poisson": segments["poisson"],
        "bursty": segments["bursty"],
        "overload": segments["overload"],
        "int8": segments["int8"],
        "acceptance": acceptance,
    }
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"# wrote {out_json}", flush=True)
    return payload


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--requests", type=int, default=2000,
                    help="requests per tenant per segment")
    ap.add_argument("--rate-rps", type=float, default=400.0,
                    help="per-tenant Poisson rate (bursty modulates it)")
    ap.add_argument("--slo-ms", type=float, default=100.0)
    ap.add_argument("--max-rows", type=int, default=24,
                    help="largest request size in the streams")
    ap.add_argument("--queue-rows", type=int, default=4096,
                    help="per-tenant admission bound (rows)")
    ap.add_argument("--epochs", type=int, default=15)
    ap.add_argument("--aligned", type=int, default=150)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: 2 training epochs, 400 requests per "
                         "tenant, 200 ms SLO (generous for the noisy "
                         "2-core runner)")
    ap.add_argument("--out", default="BENCH_load.json",
                    help="JSON output path ('' to skip)")
    args = ap.parse_args()
    if args.smoke:
        args.epochs = min(args.epochs, 2)
        args.requests = min(args.requests, 400)
        args.rate_rps = min(args.rate_rps, 200.0)
        args.slo_ms = max(args.slo_ms, 200.0)
    run(tenants=args.tenants, requests=args.requests,
        rate_rps=args.rate_rps, slo_ms=args.slo_ms, max_rows=args.max_rows,
        max_queue_rows=args.queue_rows, epochs=args.epochs,
        aligned=args.aligned, seed=args.seed, out_json=args.out)


if __name__ == "__main__":
    main()
