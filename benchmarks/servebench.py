"""Online-serving benchmark for the VFL inference subsystem
(``repro.serve.vfl``): bucketed batched engine vs naive per-request jit
dispatch, over a mixed-size request stream.

Trains a small APC-VFL model, exports its ``ModelBundle``, warms the
engine's bucket shapes, then drives a 10k-request stream whose sizes are
uniform in [1, max_rows] — the worst case for naive dispatch, which jits
once per DISTINCT request size, while the bucketer keeps every dispatch on
one of ~5 padded power-of-two shapes.  The naive baseline runs the same
jitted predict body per request at its exact shape (measured on a subset,
throughput extrapolates linearly: every request is an independent
dispatch).

Writes ``BENCH_serve.json``: throughput (rows/s, req/s), p50/p99
service-time latency PLUS the shared ``serve.metrics`` latency block
(queueing — here backlog-drain wait — and service as separate percentile
series, the same schema ``BENCH_load.json`` uses), cache hit-rate,
per-path dispatch and compile counts, an **int8 section** (the same
stream through ``VFLServingEngine(quantize="int8")`` with the pinned
``serve.quant.parity_report`` vs fp32), and the acceptance block
(distinct batch shapes <= 6, bucketed throughput >= 5x naive, int8
throughput >= 0.9x fp32 inside the parity bounds).  The live
arrival-clocked load benchmark is ``benchmarks/loadbench.py``.

Run:  PYTHONPATH=src python benchmarks/servebench.py [--smoke]
      [--requests 10000] [--max-rows 100] [--epochs 15] [--naive-sample
      400] [--out BENCH_serve.json]
"""
from __future__ import annotations

import argparse
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.core import pipeline
from repro.data.synthetic import make_dataset
from repro.data.vertical import make_scenario
from repro.serve import vfl as sv
from repro.launch.compile_cache import use_compile_cache

MAX_BATCH_SHAPES = 6          # acceptance: distinct compiled batch shapes
MIN_SPEEDUP = 5.0             # acceptance: bucketed vs naive throughput


def run(*, requests: int = 10_000, max_rows: int = 100, epochs: int = 15,
        aligned: int = 150, naive_sample: int = 400, seed: int = 0,
        p_known: float = 0.5, out_json: str = "BENCH_serve.json") -> dict:
    ds = make_dataset("bcw", seed=seed)
    sc = make_scenario(ds, n_active_features=5, n_aligned=aligned,
                       seed=seed)
    t0 = time.time()
    result = pipeline.run_apcvfl(sc, seed=seed, max_epochs=epochs)
    train_s = time.time() - t0
    bundle = sv.export_bundle(result, sc)
    print(f"# trained apcvfl in {train_s:.1f}s "
          f"(acc={result.metrics['accuracy']:.4f}); bundle: "
          f"{bundle.meta['n_cached']} cached latents", flush=True)

    stream = sv.make_request_stream(sc.active.x, sc.active.ids, requests,
                                    seed=seed + 1, max_rows=max_rows,
                                    p_known=p_known)

    # --- bucketed batched engine (warm: compiles happen per bucket) -------
    from repro.analysis import guards
    engine = sv.VFLServingEngine(bundle)
    with guards.compile_counter() as warm_tally:
        engine.warmup()
    with guards.compile_counter() as stream_tally:
        bucketed = sv.serve_stream(engine, stream)
    bucketed["xla_compiles_warmup"] = warm_tally.count
    bucketed["xla_compiles_stream"] = stream_tally.count
    print(f"servebench/bucketed/r{requests},"
          f"{1e6 * bucketed['wall_s'] / max(bucketed['rows'], 1):.1f},"
          f"rows_per_s={bucketed['rows_per_s']:.0f}|"
          f"p50={bucketed['latency_ms_p50']}ms|"
          f"p99={bucketed['latency_ms_p99']}ms|"
          f"hit_rate={bucketed['cache_hit_rate']}", flush=True)

    # --- naive per-request jit dispatch (one compile per distinct size) ---
    import jax
    naive_fn = jax.jit(sv._active_apply)      # fresh jit: separate cache
    sample = stream[:min(naive_sample, len(stream))]
    t0 = time.perf_counter()
    for r in sample:
        np.asarray(naive_fn(engine._p_active, jnp.asarray(r.x, jnp.float32)))
    naive_s = time.perf_counter() - t0
    naive_rows = int(sum(len(r.x) for r in sample))
    naive = {
        "requests": len(sample),
        "rows": naive_rows,
        "wall_s": round(naive_s, 4),
        "rows_per_s": round(naive_rows / max(naive_s, 1e-9), 1),
        "requests_per_s": round(len(sample) / max(naive_s, 1e-9), 1),
        "compiles": (int(naive_fn._cache_size())
                     if hasattr(naive_fn, "_cache_size") else None),
    }
    print(f"servebench/naive/r{len(sample)},"
          f"{1e6 * naive_s / max(naive_rows, 1):.1f},"
          f"rows_per_s={naive['rows_per_s']:.0f}|"
          f"compiles={naive['compiles']}", flush=True)

    # --- int8 quantized path: same stream, pinned fp32 parity -------------
    from repro.serve import quant
    q_engine = sv.VFLServingEngine(bundle, quantize="int8")
    q_engine.warmup()
    q_stream = sv.make_request_stream(sc.active.x, sc.active.ids, requests,
                                      seed=seed + 1, max_rows=max_rows,
                                      p_known=p_known)
    with guards.compile_counter() as q_tally:
        quantized = sv.serve_stream(q_engine, q_stream)
    quantized["xla_compiles_stream"] = q_tally.count
    parity = quant.parity_report(bundle, sc.active.x, sc.active.y,
                                 n_classes=sc.n_classes)
    quantized["parity"] = parity
    print(f"servebench/int8/r{requests},"
          f"{1e6 * quantized['wall_s'] / max(quantized['rows'], 1):.1f},"
          f"rows_per_s={quantized['rows_per_s']:.0f}|"
          f"max_dlogit={parity['max_abs_logit_delta']:.4f}|"
          f"flip_rate={parity['pred_flip_rate']:.4f}|"
          f"f1_delta={parity['f1_macro_delta']:.4f}|"
          f"compression={parity['compression']}x", flush=True)

    speedup = bucketed["rows_per_s"] / max(naive["rows_per_s"], 1e-9)
    shapes = bucketed["compiled"]["distinct_batch_shapes"]
    acceptance = {
        "distinct_batch_shapes": shapes,
        "max_batch_shapes": MAX_BATCH_SHAPES,
        "shapes_ok": shapes <= MAX_BATCH_SHAPES,
        "throughput_speedup_vs_naive": round(speedup, 2),
        "min_speedup": MIN_SPEEDUP,
        "speedup_ok": speedup >= MIN_SPEEDUP,
        "xla_compiles_stream": bucketed["xla_compiles_stream"],
        "stream_compiles_ok": bucketed["xla_compiles_stream"] == 0,
        # int8 acceptance: no slower than fp32 (pre-dequantized serving
        # params keep the jitted fp32 path; 0.9 absorbs runner noise) and
        # inside the pinned parity bounds of serve.quant
        "int8_rows_per_s": quantized["rows_per_s"],
        "int8_throughput_ratio": round(
            quantized["rows_per_s"] / max(bucketed["rows_per_s"], 1e-9), 3),
        "int8_throughput_ok":
            quantized["rows_per_s"] >= 0.9 * bucketed["rows_per_s"],
        "int8_parity_ok": (
            parity["max_abs_logit_delta"] <= quant.MAX_LOGIT_DELTA
            and parity["rel_logit_delta"] <= quant.MAX_REL_LOGIT_DELTA
            and parity["f1_macro_delta"] <= quant.MAX_F1_DELTA),
    }
    print(f"# acceptance: {shapes} batch shapes "
          f"(<= {MAX_BATCH_SHAPES}: {acceptance['shapes_ok']}), "
          f"{speedup:.1f}x naive throughput "
          f"(>= {MIN_SPEEDUP}x: {acceptance['speedup_ok']}), "
          f"{bucketed['xla_compiles_stream']} warmed-stream compiles "
          f"(== 0: {acceptance['stream_compiles_ok']}), "
          f"int8 {acceptance['int8_throughput_ratio']}x fp32 "
          f"(ok: {acceptance['int8_throughput_ok']}), "
          f"int8 parity ok: {acceptance['int8_parity_ok']}", flush=True)

    payload = {
        "name": f"servebench/bcw/r{requests}/mr{max_rows}",
        "train": {"epochs": epochs, "wall_s": round(train_s, 2),
                  "accuracy": result.metrics["accuracy"]},
        "stream": {"requests": requests, "max_rows": max_rows,
                   "p_known": p_known, "seed": seed},
        "bucketed": bucketed,
        "naive": naive,
        "int8": quantized,
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
    ap.add_argument("--requests", type=int, default=10_000)
    ap.add_argument("--max-rows", type=int, default=100)
    ap.add_argument("--epochs", type=int, default=15)
    ap.add_argument("--aligned", type=int, default=150)
    ap.add_argument("--naive-sample", type=int, default=400)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--p-known", type=float, default=0.5)
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: 2 training epochs, naive sample 200 "
                         "(the 10k-request stream is kept — it IS the "
                         "acceptance workload)")
    ap.add_argument("--out", default="BENCH_serve.json",
                    help="JSON output path ('' to skip)")
    args = ap.parse_args()
    if args.smoke:
        args.epochs = min(args.epochs, 2)
        args.naive_sample = min(args.naive_sample, 200)
    run(requests=args.requests, max_rows=args.max_rows, epochs=args.epochs,
        aligned=args.aligned, naive_sample=args.naive_sample,
        seed=args.seed, p_known=args.p_known, out_json=args.out)


if __name__ == "__main__":
    main()
