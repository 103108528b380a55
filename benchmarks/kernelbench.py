"""Kernel microbench: wall-time of the jitted jnp reference paths on CPU
(the Pallas kernels themselves are TPU-target; interpret mode timing is
not meaningful for perf, so the CSV reports the XLA-compiled reference
and the kernel/oracle max-abs-error as the derived column).

Every fused kernel gets a row: flash attention, the Eq. 5 distill loss,
the lane-MLP forward AND its closed-form VJP, the fused probe step
(loss/dW/db), and the int8 dequant matmul.  The errors are the point —
each row carries a pinned bound (``ERROR_BOUNDS``) and the run writes
``BENCH_kernels.json`` with per-kernel ``ok`` flags; CI gates on the
aggregate (``acceptance.ok``), so a kernel whose math drifts from its
oracle fails the build, not just a local test run.

Run:  PYTHONPATH=src python benchmarks/kernelbench.py
      [--out BENCH_kernels.json]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ref import (flash_attention_ref, fused_distill_loss_ref,
                               int8_matmul_ref, mlp2_ref, probe_grad_ref)
from repro.launch.compile_cache import use_compile_cache

# pinned max-abs-error bound per kernel row (vs the jnp oracle, fp32).
# lane_mlp/probe/int8 are closed-form identical math — their error is
# pure float reassociation, orders of magnitude under these bounds.
ERROR_BOUNDS = {
    "flash_attention": 1e-4,
    "fused_distill": 1e-5,
    "lane_mlp_fwd": 1e-4,
    "lane_mlp_grad": 1e-5,     # relative (see the grad row below)
    "probe_step": 1e-4,
    "int8_matmul": 1e-5,
}


def _time(f, *args, n=5):
    f(*args)  # compile + warm
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def _maxerr(*pairs) -> float:
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in pairs)


def run(csv=True, out_json: str = "BENCH_kernels.json"):
    if csv:
        print("name,us_per_call,derived")
    key = jax.random.PRNGKey(0)
    rows = []

    B, H, S, hd = 2, 4, 512, 64
    q, k, v = [jax.random.normal(kk, (B, H, S, hd))
               for kk in jax.random.split(key, 3)]
    ref = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
    us = _time(ref, q, k, v)
    kern = ops.flash_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                               jnp.swapaxes(v, 1, 2), causal=True)
    err = _maxerr((jnp.swapaxes(kern, 1, 2), ref(q, k, v)))
    rows.append(("flash_attention", us, err))

    Bd, D, M = 4096, 32, 256
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (Bd, D))
    xh = jax.random.normal(ks[1], (Bd, D))
    z = jax.random.normal(ks[2], (Bd, M))
    zt = jax.random.normal(ks[3], (Bd, M))
    mask = (jax.random.uniform(ks[4], (Bd,)) > 0.5).astype(jnp.float32)
    ref2 = jax.jit(lambda *a: fused_distill_loss_ref(*a, lam=0.01))
    us2 = _time(ref2, x, xh, z, zt, mask)
    err2 = _maxerr((ops.fused_distill_loss(x, xh, z, zt, mask),
                    ref2(x, xh, z, zt, mask)))
    rows.append(("fused_distill", us2, err2))

    # --- lane MLP: fused 2-layer forward + closed-form VJP ---------------
    km = jax.random.split(key, 6)
    Bm, din, dh, dout = 256, 30, 64, 128
    mx = jax.random.normal(km[0], (Bm, din))
    w0 = jax.random.normal(km[1], (din, dh)) / jnp.sqrt(din)
    b0 = jax.random.normal(km[2], (dh,)) * 0.1
    w1 = jax.random.normal(km[3], (dh, dout)) / jnp.sqrt(dh)
    b1 = jax.random.normal(km[4], (dout,)) * 0.1
    mref = jax.jit(mlp2_ref)
    us3 = _time(mref, mx, w0, b0, w1, b1)
    err3 = _maxerr((ops.fused_mlp2(mx, w0, b0, w1, b1),
                    mref(mx, w0, b0, w1, b1)))
    rows.append(("lane_mlp_fwd", us3, err3))

    # grad row: RELATIVE error (sum-of-squares grads scale with the
    # output magnitude; absolute error would track that scale, not the
    # kernel's accuracy)
    loss_k = lambda *a: jnp.sum(jnp.square(ops.fused_mlp2(*a)))
    loss_r = lambda *a: jnp.sum(jnp.square(mlp2_ref(*a)))
    gref = jax.jit(jax.grad(loss_r, argnums=(0, 1, 2, 3, 4)))
    us4 = _time(gref, mx, w0, b0, w1, b1)
    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3, 4))(mx, w0, b0, w1, b1)
    gr = gref(mx, w0, b0, w1, b1)
    err4 = max(
        float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(b)),
                                                    1.0))
        for a, b in zip(gk, gr))
    rows.append(("lane_mlp_grad", us4, err4))

    # --- fused probe step: loss/dW/db in one pass ------------------------
    kp = jax.random.split(key, 4)
    n, d, c = 512, 128, 4
    px = jax.random.normal(kp[0], (n, d))
    pw = jax.random.normal(kp[1], (d, c)) * 0.1
    pb = jax.random.normal(kp[2], (c,)) * 0.1
    py = jax.random.randint(kp[3], (n,), 0, c)
    prw = (jax.random.uniform(key, (n,)) > 0.3).astype(jnp.float32)
    pref = jax.jit(probe_grad_ref)
    us5 = _time(pref, pw, pb, px, py, prw)
    got = ops.probe_grad_step(pw, pb, px, py, prw)
    want = pref(pw, pb, px, py, prw)
    err5 = _maxerr(*zip(got, want))
    rows.append(("probe_step", us5, err5))

    # --- int8 dequant matmul (the quantized serving GEMM) ----------------
    ki = jax.random.split(key, 3)
    xi = jax.random.normal(ki[0], (256, 128))
    wf = jax.random.normal(ki[1], (128, 64))
    scale = jnp.max(jnp.abs(wf), axis=0) / 127.0
    wq = jnp.clip(jnp.round(wf / scale[None, :]), -127, 127).astype(jnp.int8)
    bi = jax.random.normal(ki[2], (64,)) * 0.1
    iref = jax.jit(int8_matmul_ref)
    us6 = _time(iref, xi, wq, scale, bi)
    err6 = _maxerr((ops.int8_matmul(xi, wq, scale, bi),
                    iref(xi, wq, scale, bi)))
    rows.append(("int8_matmul", us6, err6))

    recs = []
    for name, us, err in rows:
        bound = ERROR_BOUNDS[name]
        recs.append({"kernel": name, "ref_us_per_call": round(us, 1),
                     "max_abs_err": err, "bound": bound,
                     "ok": err <= bound})
        print(f"kernel/{name}_ref_cpu,{us:.1f},maxerr={err:.2e}|"
              f"bound={bound:.0e}|ok={err <= bound}", flush=True)
    payload = {
        "name": "kernelbench/cpu-interpret",
        "backend": jax.default_backend(),
        "kernels": recs,
        "acceptance": {"all_within_bounds": all(r["ok"] for r in recs),
                       "ok": all(r["ok"] for r in recs)},
    }
    if out_json:
        with open(out_json, "w") as fh:
            json.dump(payload, fh, indent=1)
        print(f"# wrote {out_json}", flush=True)
    return payload


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_kernels.json",
                    help="JSON output path ('' to skip)")
    args = ap.parse_args()
    run(out_json=args.out)


if __name__ == "__main__":
    main()
