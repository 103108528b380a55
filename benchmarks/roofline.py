"""Roofline analysis of the APC-VFL pipeline stages.

For each serving/training stage actually run by this repo — the g1
autoencoder steps, the g2 joint step, the g3 distillation step, the CV
probe step, and the two serving paths — the compiled HLO's FLOPs and
bytes come straight from ``jit(fn).lower(...).compile().cost_analysis()``
(no hand-derived counts): arithmetic intensity = flops / bytes, compared
against the machine balance point (ridge) ``PEAK_FLOPS_BF16 / HBM_BW`` of
the v5e hardware model in ``repro.configs.base``.  Stages left of the
ridge are memory-bound — the ones the fused Pallas kernels
(``kernels.lane_mlp`` / ``kernels.probe`` / ``kernels.int8_matmul``)
exist to help, by collapsing per-op HBM round-trips into one pass.

The int8 serving stage is derived from the fp32 serve cost analytically
(same FLOPs; weight traffic divided by 4, the whole point of
``serve.quant``) because the int8 GEMM lives in a Pallas kernel that the
CPU backend only runs interpreted — its ``source`` field says so.

Writes ``BENCH_roofline.json`` and prints the repo's
``name,us_per_call,derived`` CSV.

The pre-VFL dry-run mode (per arch x shape x mesh JSONs produced by
``repro.launch.dryrun`` for the transformer stack) survives as
``--mode dryrun`` / ``run_dryrun()``; it now FAILS LOUDLY when the
artifact directory is empty or a record references a shape missing from
``INPUT_SHAPES`` instead of silently analyzing nothing.

Run:  PYTHONPATH=src python benchmarks/roofline.py [--batch 32]
      [--serve-batch 256] [--out BENCH_roofline.json]
      PYTHONPATH=src python benchmarks/roofline.py --mode dryrun \
          [--dryrun-dir experiments/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import HBM_BW, ICI_BW, INPUT_SHAPES, PEAK_FLOPS_BF16
from repro.launch.compile_cache import use_compile_cache

N_LINKS = 3   # ICI links per v5e chip usable concurrently (2D torus + wrap)

RIDGE = PEAK_FLOPS_BF16 / HBM_BW          # flops/byte at machine balance


# ---------------------------------------------------------------------------
# VFL-stage mode (default): cost_analysis over the real pipeline stages
# ---------------------------------------------------------------------------

def _cost(fn, *args) -> dict:
    """FLOPs / bytes of the compiled HLO for ``fn(*args)``.  Fails with a
    named error if the backend's cost model omits the keys (rather than
    writing zeros that would classify every stage as infinitely
    compute-bound)."""
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    if not ca or "flops" not in ca or "bytes accessed" not in ca:
        raise RuntimeError(
            f"cost_analysis on backend {jax.default_backend()!r} did not "
            f"report flops/bytes (got keys {sorted(ca or {})}); the "
            f"roofline needs a backend with an XLA cost model")
    return {"flops": float(ca["flops"]),
            "bytes": float(ca["bytes accessed"])}


def _classify(stage: str, flops: float, nbytes: float, *,
              source: str = "cost_analysis", note: str = "") -> dict:
    intensity = flops / max(nbytes, 1.0)
    t_comp = flops / PEAK_FLOPS_BF16
    t_mem = nbytes / HBM_BW
    rec = {
        "stage": stage,
        "flops": flops,
        "bytes": nbytes,
        "intensity_flops_per_byte": round(intensity, 3),
        "ridge_flops_per_byte": round(RIDGE, 1),
        "bound": "compute" if intensity >= RIDGE else "memory",
        "t_compute_s": t_comp,
        "t_memory_s": t_mem,
        "step_time_bound_s": max(t_comp, t_mem),
        "source": source,
    }
    if note:
        rec["note"] = note
    return rec


def vfl_stages(batch: int = 32, serve_batch: int = 256,
               probe_rows: int = 512, seed: int = 0) -> list:
    """Cost records for the pipeline stages at bcw-like shapes: active
    d=5, passive d=25, Table-3 widths, g3 latent 256, binary head."""
    from repro.core import autoencoder as ae
    from repro.core import distill
    from repro.kernels import ref
    from repro.serve import vfl as sv

    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    rng = np.random.RandomState(seed)
    f32 = lambda *shp: jnp.asarray(rng.randn(*shp).astype(np.float32))

    recs = []

    # --- training steps: value+grad of each stage's loss ------------------
    ae_stages = [
        ("g1_active_step", ae.init_autoencoder(k1, [5, 64, 128]), 5),
        ("g1_passive_step", ae.init_autoencoder(k2, [25, 128, 256]), 25),
        ("g2_step", ae.init_autoencoder(k3, [384, 256, 256]), 384),
    ]
    grad_recon = jax.value_and_grad(ae.recon_loss)
    for name, params, d in ae_stages:
        c = _cost(grad_recon, params, {"x": f32(batch, d)})
        recs.append(_classify(name, c["flops"], c["bytes"]))

    g3 = ae.init_autoencoder(k4, [5, 256, 256])
    dbatch = {"x": f32(batch, 5), "z_teacher": f32(batch, 256),
              "aligned": jnp.ones((batch,), jnp.float32)}
    c = _cost(jax.value_and_grad(distill.distill_loss), g3, dbatch)
    recs.append(_classify("g3_distill_step", c["flops"], c["bytes"]))

    # --- probe step: the fused-kernel semantics via its jnp oracle --------
    w = f32(256, 2)
    b = f32(2)
    px = f32(probe_rows, 256)
    py = jnp.asarray(rng.randint(0, 2, probe_rows), jnp.int32)
    prw = jnp.ones((probe_rows,), jnp.float32)
    c = _cost(ref.probe_grad_ref, w, b, px, py, prw)
    recs.append(_classify("probe_step", c["flops"], c["bytes"]))

    # --- serving: head(g3(x)) at the largest bucket shape -----------------
    p_active = {
        "g3": {"enc": g3["enc"]},
        "head": {"w": w, "b": b},
        "mean": jnp.zeros((5,), jnp.float32),
        "inv_scale": jnp.ones((5,), jnp.float32),
    }
    sx = f32(serve_batch, 5)
    c = _cost(sv._active_apply, p_active, sx)
    recs.append(_classify("serve_active", c["flops"], c["bytes"]))

    # int8 serving: identical FLOPs, weight traffic / 4 (1 byte/param +
    # one fp32 scale per output channel instead of 4 bytes/param)
    w_params = 5 * 256 + 256 * 256 + 256 * 2
    w_bytes_fp32 = 4.0 * w_params
    w_bytes_int8 = 1.0 * w_params + 4.0 * (256 + 256 + 2)
    recs.append(_classify(
        "serve_int8", c["flops"],
        c["bytes"] - w_bytes_fp32 + w_bytes_int8,
        source="analytic-int8",
        note="fp32 serve cost with weight traffic at 1 byte/param "
             "(kernels.int8_matmul dequantizes in-tile)"))
    return recs


def run(batch: int = 32, serve_batch: int = 256, probe_rows: int = 512,
        seed: int = 0, csv: bool = True,
        out_json: str = "BENCH_roofline.json") -> list:
    recs = vfl_stages(batch=batch, serve_batch=serve_batch,
                      probe_rows=probe_rows, seed=seed)
    if csv:
        print("name,us_per_call,derived")
    for r in recs:
        print(f"roofline/{r['stage']},{r['step_time_bound_s']*1e6:.2f},"
              f"bound={r['bound']}|"
              f"ai={r['intensity_flops_per_byte']:.1f}|"
              f"ridge={r['ridge_flops_per_byte']:.0f}|"
              f"flops={r['flops']:.3e}|bytes={r['bytes']:.3e}",
              flush=True)
    if out_json:
        payload = {
            "name": f"roofline/vfl/b{batch}/sb{serve_batch}",
            "machine": {"peak_flops_bf16": PEAK_FLOPS_BF16,
                        "hbm_bw": HBM_BW,
                        "ridge_flops_per_byte": round(RIDGE, 1)},
            "config": {"batch": batch, "serve_batch": serve_batch,
                       "probe_rows": probe_rows, "seed": seed,
                       "backend": jax.default_backend()},
            "stages": recs,
        }
        with open(out_json, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"# wrote {out_json}", flush=True)
    return recs


# ---------------------------------------------------------------------------
# legacy dry-run mode: per (arch x shape x mesh) transformer artifacts
# ---------------------------------------------------------------------------

def model_flops(rec: dict) -> float:
    """6*N*D for train (fwd+bwd), 2*N*D for inference, per STEP (global)."""
    if rec["shape"] not in INPUT_SHAPES:
        raise KeyError(
            f"dry-run record references shape {rec['shape']!r} which is "
            f"not in repro.configs.base.INPUT_SHAPES "
            f"({sorted(INPUT_SHAPES)}); the artifact is stale — "
            f"regenerate it with repro.launch.dryrun")
    shape = INPUT_SHAPES[rec["shape"]]
    n = rec["active_params"]
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token per row

def analyze_record(rec: dict) -> dict:
    chips = rec["n_chips"]
    t_comp = rec["hlo_flops_per_device"] / PEAK_FLOPS_BF16
    t_mem = rec["hlo_bytes_per_device"] / HBM_BW
    t_coll = rec["collective_bytes_per_device"] / (N_LINKS * ICI_BW)
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dom = max(terms, key=terms.get)
    mf = model_flops(rec)
    hlo_global = rec["hlo_flops_per_device"] * chips
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "objective": rec.get("objective", "lm"),
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "bottleneck": dom,
        "model_flops": mf,
        "useful_fraction": mf / hlo_global if hlo_global else 0.0,
        "mem_temp_gb": rec["mem_temp_bytes"] / 1e9,
        "mem_args_gb": rec["mem_argument_bytes"] / 1e9,
        "fits_hbm16": (rec["mem_temp_bytes"] + rec["mem_argument_bytes"])
        < 16e9,
        "step_time_bound_s": max(terms.values()),
    }


def run_dryrun(dryrun_dir: str = "experiments/dryrun", csv: bool = True,
               mesh_filter: str = "16x16"):
    paths = sorted(glob.glob(os.path.join(dryrun_dir, "*.json")))
    if not paths:
        raise FileNotFoundError(
            f"no dry-run artifacts under {dryrun_dir!r} — this mode "
            f"analyzes per (arch x shape x mesh) JSONs written by "
            f"repro.launch.dryrun; for the VFL pipeline roofline run "
            f"the default mode (no --mode dryrun) instead")
    recs = []
    for path in paths:
        with open(path) as fh:
            rec = json.load(fh)
        if mesh_filter and rec.get("mesh") != mesh_filter:
            continue
        recs.append(analyze_record(rec))
    if not recs:
        raise ValueError(
            f"{len(paths)} dry-run artifacts under {dryrun_dir!r} but "
            f"none match mesh_filter={mesh_filter!r}; pass "
            f"mesh_filter='' to analyze all meshes")
    if csv:
        print("name,us_per_call,derived")
        for r in recs:
            tag = f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}"
            print(f"{tag},{r['step_time_bound_s']*1e6:.0f},"
                  f"bound={r['bottleneck']}|"
                  f"Tc={r['t_compute_s']:.3e}|Tm={r['t_memory_s']:.3e}|"
                  f"Tx={r['t_collective_s']:.3e}|"
                  f"useful={r['useful_fraction']:.2f}|"
                  f"fits16G={'Y' if r['fits_hbm16'] else 'N'}")
    return recs


def markdown_table(recs: list) -> str:
    lines = ["| arch | shape | mesh | compute s | memory s | collective s | "
             "bottleneck | useful | args GB | temp GB | fits 16G |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in recs:
        arch = r["arch"] + (" (distill)" if r.get("objective") not in
                            (None, "lm") else "")
        lines.append(
            f"| {arch} | {r['shape']} | {r['mesh']} | "
            f"{r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} | "
            f"{r['t_collective_s']:.3e} | {r['bottleneck']} | "
            f"{r['useful_fraction']:.2f} | {r['mem_args_gb']:.1f} | "
            f"{r['mem_temp_gb']:.1f} | {'Y' if r['fits_hbm16'] else 'N'} |")
    return "\n".join(lines)


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["vfl", "dryrun"], default="vfl")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--serve-batch", type=int, default=256)
    ap.add_argument("--probe-rows", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_roofline.json",
                    help="JSON output path ('' to skip; vfl mode only)")
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument("--mesh-filter", default="16x16")
    args = ap.parse_args()
    if args.mode == "dryrun":
        run_dryrun(args.dryrun_dir, mesh_filter=args.mesh_filter)
    else:
        run(batch=args.batch, serve_batch=args.serve_batch,
            probe_rows=args.probe_rows, seed=args.seed, out_json=args.out)


if __name__ == "__main__":
    main()
