"""Robustness benchmark: the utility-vs-leakage frontier of the hardened
exchange, and SLO attainment of the serving runtime under mid-stream
passive-party faults.  The subsystem's two claims in one artifact:

* **frontier** — the defense grid (Gaussian sigma sweep + int8/sign
  quantization points) run twice: utility via
  ``robustness.defense.dp_frontier`` (the WHOLE sigma grid as replica
  lanes of one protocol — one compile per stage), leakage via
  ``robustness.attacks.leakage_profile`` (every registered attack against
  every defense, surfaces lane-batched).  CI gates that leakage is
  NON-INCREASING in sigma for the inversion and membership attacks
  (membership starts at ~1.0 undefended — aligned rows match their own
  exchanged latents exactly — so the frontier must visibly close).

* **faulted serving** — multi-tenant Poisson load with a seeded
  ``FaultPlan`` injected mid-stream: one tenant's passive party drops
  out (never recovers), another goes stale then recovers.  Gates: SLO
  attainment >= the ``robust_stream`` budget, ZERO steady-state XLA
  compiles (the degrade path reuses warmed active-path executables),
  zero collaborative dispatches while faulted (degraded tenants serve
  the active-only fallback — NEVER stale latents), the recovered tenant
  resumes with a bumped cache version, and unfaulted tenants stay
  bit-identical to dedicated serving (parity replay; faulted tenants are
  excluded — a fresh solo engine has a fresh cache, so divergence there
  is the DEFENSE working, not a bug).

* **training faults** — ``run_faulted_apcvfl`` under dropout / stale /
  drift exchange events: every degraded run completes and reports its
  ``fault_*`` flags; dropout is exactly the active-only ablation
  (0 data rounds).

Writes ``BENCH_robust.json`` with the acceptance block gated in CI.

Run:  PYTHONPATH=src python benchmarks/robustbench.py [--smoke]
      [--epochs 15] [--requests 1200] [--out BENCH_robust.json]
"""
from __future__ import annotations

import argparse
import json
import time
import warnings

from repro.analysis import guards
from repro.core import pipeline
from repro.data.synthetic import make_dataset
from repro.data.vertical import make_scenario
from repro.robustness import attacks, defense, faults
from repro.serve import runtime as rt
from repro.serve import vfl as sv
from repro.launch.compile_cache import use_compile_cache

SIGMAS = (0.0, 0.5, 2.0, 8.0)
MONOTONE_TOL = 0.05      # attacks are trained estimators; small jitter ok


def _monotone_nonincreasing(xs, tol: float = MONOTONE_TOL) -> bool:
    return all(b <= a + tol for a, b in zip(xs, xs[1:]))


def run_frontier(*, sigmas=SIGMAS, epochs: int = 15, aligned: int = 150,
                 n_aux: int = 64, seed: int = 0) -> dict:
    ds = make_dataset("bcw", seed=seed)
    sc = make_scenario(ds, n_active_features=5, n_aligned=aligned,
                       seed=seed)
    t0 = time.time()

    # utility: the whole sigma grid as replica lanes of one protocol
    util = defense.dp_frontier(sc, list(sigmas), seed=seed,
                               max_epochs=epochs)
    # quantization points (distinct wire dtypes, accounted per dtype)
    quant_points = {}
    for mode in ("int8", "sign"):
        r = defense.run_apcvfl_dp(sc, quantize=mode, seed=seed,
                                  max_epochs=epochs)
        quant_points[mode] = {
            "accuracy": r.metrics["accuracy"],
            "f1_macro": r.metrics["f1_macro"],
            "exchange_bytes": r.metrics["exchange_bytes"],
            "by_dtype": r.comm["by_dtype"],
        }

    # leakage: every registered attack against every sigma, lane-batched
    transforms = [defense.make_transform(sigma=float(s)) for s in sigmas]
    with warnings.catch_warnings():
        # n_aux clamping on small aligned sets is expected here; the
        # effective budget is recorded in each report
        warnings.simplefilter("ignore", RuntimeWarning)
        profile = attacks.leakage_profile(sc, transforms, seed=seed,
                                          n_aux=n_aux, max_epochs=epochs)

    points = []
    for s, r, reps in zip(sigmas, util, profile):
        points.append({
            "sigma": float(s),
            "accuracy": r.metrics["accuracy"],
            "f1_macro": r.metrics["f1_macro"],
            "exchange_bytes": r.metrics["exchange_bytes"],
            "leakage": {name: rep.metrics()
                        for name, rep in reps.items()},
        })
        print(f"robustbench/frontier,sigma={s:g}|"
              f"acc={r.metrics['accuracy']:.4f}|"
              + "|".join(f"{n}={rep.leakage:.3f}"
                         for n, rep in sorted(reps.items())), flush=True)

    leak = {name: [p["leakage"][name]["leakage"] for p in points]
            for name in profile[0]}
    gates = {
        "inversion_monotone": _monotone_nonincreasing(leak["inversion"]),
        "membership_monotone": _monotone_nonincreasing(leak["membership"]),
        "membership_open_undefended": leak["membership"][0] >= 0.9,
        "membership_closed_at_max_sigma": leak["membership"][-1]
            <= 0.5 * leak["membership"][0],
        "inversion_closed_at_max_sigma": leak["inversion"][-1]
            <= max(0.5 * leak["inversion"][0], 0.05),
    }
    return {"sigmas": list(sigmas), "points": points,
            "quantized": quant_points, "gates": gates,
            "wall_s": round(time.time() - t0, 2)}


def run_faulted_serving(*, tenants: int = 3, requests: int = 1200,
                        rate_rps: float = 300.0, slo_ms: float = 100.0,
                        max_rows: int = 24, epochs: int = 15,
                        aligned: int = 150, seed: int = 0) -> dict:
    if tenants < 3:
        raise ValueError("robustbench needs >= 3 tenants: one dropout, "
                         "one stale+recover, one healthy control")
    budgets = guards.load_budgets()["robust_stream"]
    bundles, scenarios = {}, {}
    for k in range(tenants):
        name = f"tenant{k}"
        ds = make_dataset("bcw", seed=seed + k)
        sc = make_scenario(ds, n_active_features=5, n_aligned=aligned,
                           seed=seed + k)
        result = pipeline.run_apcvfl(sc, seed=seed + k, max_epochs=epochs)
        bundles[name] = sv.export_bundle(result, sc)
        scenarios[name] = sc

    registry = rt.TenantRegistry()
    for name, b in bundles.items():
        registry.register(name, b)
    with guards.compile_counter() as warm:
        registry.warmup()
    warm_compiles = warm.count          # snapshot: the tally is live

    streams = []
    for k, name in enumerate(registry.names()):
        sc = scenarios[name]
        streams.append(rt.make_timed_stream(
            sc.active.x, sc.active.ids, requests, tenant=name,
            arrivals="poisson", rate_rps=rate_rps, seed=seed + 101 * k,
            max_rows=max_rows))
    merged = rt.merge_streams(*streams)
    # faults land mid-stream: dropout at ~1/3, stale at ~1/2 with a
    # recovery at ~3/4 of the arrival horizon
    horizon = merged[-1].t_arrival_ms
    plan = faults.FaultPlan(name="robustbench-midstream", seed=seed, events=(
        faults.FaultEvent(kind="dropout", t_ms=horizon / 3,
                          tenant="tenant1"),
        faults.FaultEvent(kind="stale", t_ms=horizon / 2,
                          tenant="tenant2"),
        faults.FaultEvent(kind="recover", t_ms=0.75 * horizon,
                          tenant="tenant2"),
    ))

    runtime = rt.ServingRuntime(
        registry, rt.RuntimeConfig(slo_ms=slo_ms))
    registry.reset_stats()
    with guards.compile_counter() as steady:
        report = runtime.run(merged, faults=plan)
    report["xla_compiles_stream"] = steady.count
    # parity replay ONLY for unfaulted tenants: a fresh solo engine has a
    # fresh (non-invalidated) cache, so faulted tenants' active-only
    # logits rightly differ from dedicated serving — that divergence is
    # the degrade path working
    faulted = {e.tenant for e in plan.events if e.kind != "recover"}
    healthy = {n: b for n, b in bundles.items() if n not in faulted}
    report["parity"] = rt.verify_dispatch_parity(runtime, healthy)

    fb = report["faults"]["tenants"]
    stats = {n: registry[n].stats for n in registry.names()}
    gates = {
        "slo_attainment": report["slo"]["attainment"],
        "slo_ok": report["slo"]["attainment"]
            >= budgets["slo_attainment_min"],
        "stream_compiles": report["xla_compiles_stream"],
        "stream_compiles_ok": report["xla_compiles_stream"]
            <= budgets["warm_compiles"],
        "no_stale_serving": all(
            fb[n]["collab_dispatches_while_faulted"] == 0 for n in fb),
        "dropout_degraded": (
            fb["tenant1"]["cache_stale"]
            and stats["tenant1"].dispatches.get("active", 0) > 0),
        "dropout_had_collab_before_fault":
            stats["tenant1"].dispatches.get("collab", 0) > 0,
        "recovered_resumed": (
            not fb["tenant2"]["cache_stale"]
            and fb["tenant2"]["cache_version"] >= 2),
        "healthy_collab_served":
            stats["tenant0"].dispatches.get("collab", 0) > 0,
        "healthy_parity_bit_identical": all(
            t["bit_identical"] for t in report["parity"].values()),
    }
    print(f"robustbench/faulted/t{tenants}x{requests},"
          f"slo={gates['slo_attainment']}|"
          f"compiles={gates['stream_compiles']}|"
          f"stale_serving_violations="
          f"{sum(fb[n]['collab_dispatches_while_faulted'] for n in fb)}|"
          f"dropout_degraded={gates['dropout_degraded']}|"
          f"recovered={gates['recovered_resumed']}", flush=True)
    return {"plan": plan.to_dict(), "warm_compiles": warm_compiles,
            "report": report, "gates": gates}


def run_training_faults(*, epochs: int = 15, aligned: int = 150,
                        seed: int = 0) -> dict:
    ds = make_dataset("bcw", seed=seed)
    sc = make_scenario(ds, n_active_features=5, n_aligned=aligned,
                       seed=seed)
    clean = pipeline.run_apcvfl(sc, seed=seed, max_epochs=epochs)
    out = {"clean_accuracy": clean.metrics["accuracy"], "runs": {}}
    plans = {
        "dropout": faults.FaultPlan("dropout", events=(
            faults.FaultEvent(kind="dropout", stage="exchange"),)),
        "stale": faults.FaultPlan("stale", events=(
            faults.FaultEvent(kind="stale", stage="exchange", epochs=1),)),
        "drift": faults.FaultPlan("drift", events=(
            faults.FaultEvent(kind="drift", stage="exchange", drift=0.5),)),
    }
    for name, plan in plans.items():
        r = faults.run_faulted_apcvfl(sc, plan, seed=seed,
                                      max_epochs=epochs)
        out["runs"][name] = {
            "accuracy": r.metrics["accuracy"],
            "rounds": r.rounds,
            "flags": {k: v for k, v in r.metrics.items()
                      if k.startswith("fault_")},
        }
        print(f"robustbench/trainfault/{name},"
              f"acc={r.metrics['accuracy']:.4f}|rounds={r.rounds}",
              flush=True)
    out["gates"] = {
        "dropout_is_ablation": out["runs"]["dropout"]["rounds"] == 0,
        "all_complete": all(v["accuracy"] > 0.5
                            for v in out["runs"].values()),
    }
    return out


def run(*, epochs: int = 15, requests: int = 1200, rate_rps: float = 300.0,
        slo_ms: float = 100.0, aligned: int = 150, seed: int = 0,
        out_json: str = "BENCH_robust.json") -> dict:
    frontier = run_frontier(epochs=epochs, aligned=aligned, seed=seed)
    serving = run_faulted_serving(requests=requests, rate_rps=rate_rps,
                                  slo_ms=slo_ms, epochs=epochs,
                                  aligned=aligned, seed=seed)
    training = run_training_faults(epochs=epochs, aligned=aligned,
                                   seed=seed)
    acceptance = {
        **{f"frontier_{k}": v for k, v in frontier["gates"].items()},
        **{f"serving_{k}": v for k, v in serving["gates"].items()
           if isinstance(v, bool)},
        **{f"training_{k}": v for k, v in training["gates"].items()},
    }
    acceptance["ok"] = all(acceptance.values())
    print(f"# acceptance: ok={acceptance['ok']} " + " ".join(
        f"{k}={v}" for k, v in acceptance.items() if k != "ok"),
        flush=True)
    payload = {
        "name": f"robustbench/bcw/e{epochs}/r{requests}",
        "config": {"epochs": epochs, "requests": requests,
                   "rate_rps": rate_rps, "slo_ms": slo_ms,
                   "aligned": aligned, "seed": seed},
        "frontier": frontier,
        "faulted_serving": serving,
        "training_faults": training,
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
    ap.add_argument("--epochs", type=int, default=15)
    ap.add_argument("--requests", type=int, default=1200,
                    help="requests per tenant in the faulted segment")
    ap.add_argument("--rate-rps", type=float, default=300.0)
    ap.add_argument("--slo-ms", type=float, default=100.0)
    ap.add_argument("--aligned", type=int, default=150)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: 2 training epochs, 300 requests per "
                         "tenant, 200 ms SLO")
    ap.add_argument("--out", default="BENCH_robust.json",
                    help="JSON output path ('' to skip)")
    args = ap.parse_args()
    if args.smoke:
        args.epochs = min(args.epochs, 2)
        args.requests = min(args.requests, 300)
        args.rate_rps = min(args.rate_rps, 200.0)
        args.slo_ms = max(args.slo_ms, 200.0)
    run(epochs=args.epochs, requests=args.requests, rate_rps=args.rate_rps,
        slo_ms=args.slo_ms, aligned=args.aligned, seed=args.seed,
        out_json=args.out)


if __name__ == "__main__":
    main()
