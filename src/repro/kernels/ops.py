"""Jit'd public wrappers around the Pallas kernels.

Each call picks its mode from the backend it runs on: compiled through
Mosaic on a TPU, Pallas interpret mode on the CPU (where the tests run,
under ``JAX_PLATFORMS=cpu``), and an error on any other backend, so no
path falls back to interpreting quietly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import distill_loss as _dl
from repro.kernels import flash_attention as _fa


def _interpret() -> bool:
    """True on the CPU, False on a TPU; other backends have no kernel path."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"the Pallas kernels target TPU (compiled) or CPU "
                       f"(interpret mode); no path for backend {platform!r}")


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128):
    """q/k/v: (B, S, H, hd) [model layout] -> (B, S, H, hd)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    S = qt.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, S)
    out = _fa.flash_attention(qt, kt, vt, causal=causal, window=window,
                              block_q=bq, block_k=bk, interpret=_interpret())
    return jnp.swapaxes(out, 1, 2)


def fused_distill_rows(x, x_hat, z, z_t, mask, *, lam: float = 0.01,
                       kind: str = "mse"):
    """Per-row Eq. 5 losses (differentiable; closed-form custom VJP)."""
    return _dl.fused_distill_rows(x, x_hat, z, z_t, mask, lam=lam, kind=kind,
                                  interpret=_interpret())


def fused_distill_loss(x, x_hat, z, z_t, mask, *, lam: float = 0.01,
                       kind: str = "mse"):
    return jnp.mean(fused_distill_rows(x, x_hat, z, z_t, mask, lam=lam,
                                       kind=kind))


def fused_mlp2(x, w0, b0, w1, b1, *, final_act: bool = False,
               block_b: int = 128):
    """Fused 2-layer SELU MLP step (differentiable; closed-form custom
    VJP).  Lane axis enters the kernel grid via ``jax.vmap``."""
    from repro.kernels import lane_mlp as _lm
    return _lm.fused_mlp2(x, w0, b0, w1, b1, final_act=final_act,
                          block_b=block_b, interpret=_interpret())


def fused_lane_mlp2(xs, w0s, b0s, w1s, b1s, live, *,
                    final_act: bool = False, block_b: int = 128):
    """Explicit lane-stacked fused MLP: (L, B, din) on a lane-major grid;
    dead lanes (live=0) produce exact zeros."""
    from repro.kernels import lane_mlp as _lm
    return _lm.fused_lane_mlp2(xs, w0s, b0s, w1s, b1s, live,
                               final_act=final_act, block_b=block_b,
                               interpret=_interpret())


def probe_grad_step(w, b, x, y, rw, *, l2: float = 1e-4,
                    block_b: int = 128):
    """Fused weighted softmax-CE probe step: (loss, dW, db) in one pass."""
    from repro.kernels import probe as _pr
    return _pr.probe_grad_step(w, b, x, y, rw, l2=l2, block_b=block_b,
                               interpret=_interpret())


def int8_matmul(x, w_q, scale, b, *, act: str = "none",
                block_b: int = 128):
    """Weight-only int8 matmul with fused per-channel dequant (+ optional
    fused SELU) — the quantized serving path's GEMM."""
    from repro.kernels import int8_matmul as _i8
    return _i8.int8_matmul(x, w_q, scale, b, act=act, block_b=block_b,
                           interpret=_interpret())


def decode_attention(q, k, v, slot_pos, pos, *, window: int = 0,
                     block_w: int = 512):
    """One-token cache attention. q: (B, H, hd); k/v: (B, W, H, hd) with kv
    heads already GQA-expanded; slot_pos: (W,); pos: scalar."""
    from repro.kernels import decode_attention as _da
    B, H, hd = q.shape
    W = k.shape[1]
    qf = q.reshape(B * H, hd)
    kf = jnp.swapaxes(k, 1, 2).reshape(B * H, W, hd)
    vf = jnp.swapaxes(v, 1, 2).reshape(B * H, W, hd)
    out = _da.decode_attention(qf, kf, vf, slot_pos, pos, window=window,
                               block_w=min(block_w, W), interpret=_interpret())
    return out.reshape(B, H, hd)
