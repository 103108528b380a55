"""Fused int8-dequant matmul for the quantized serving path.

The int8 export (``serve.quant``) stores every weight matrix as
per-output-channel symmetric int8 (``w_q`` int8 + ``scale`` fp32, one
scale per column).  Serving then needs ``x @ (w_q * scale) + b`` — naively
that materializes a dequantized fp32 copy of the weights in HBM before
the matmul.  This kernel fuses the dequant into the matmul tile: the int8
weight block is upcast and scaled in registers, multiplied, and never
written back, so the weight traffic stays at 1 byte/param (the whole
point of int8 serving on a memory-bound host).

Grid is row-blocked over the batch like ``kernels.lane_mlp``; the weight
(and its scale row) ride along as full blocks.  An optional fused SELU
covers the hidden layer of the Table-3 2-layer encoders so the quantized
``head(g3(x))`` path is two kernel launches + one head launch with no
elementwise pass between them.  Semantics pinned by
``kernels.ref.int8_matmul_ref`` (+ ``jax.nn.selu`` for ``act='selu'``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SELU_ALPHA = 1.6732632423543772848170429916717
_SELU_SCALE = 1.0507009873554804934193349852946


def _selu(a):
    # exp(a) - 1: the form Mosaic lowers (see kernels.lane_mlp._selu)
    return _SELU_SCALE * jnp.where(a > 0, a, _SELU_ALPHA * (jnp.exp(a) - 1.0))


def _int8_kernel(x_ref, wq_ref, scale_ref, b_ref, o_ref, *, act):
    x = x_ref[...].astype(jnp.float32)
    # dequantize the weight tile in registers: int8 -> fp32 * column scale
    w = wq_ref[...].astype(jnp.float32) * scale_ref[...][None, :]
    out = jnp.dot(x, w, preferred_element_type=jnp.float32) + b_ref[...]
    o_ref[...] = _selu(out) if act == "selu" else out


@functools.partial(jax.jit,
                   static_argnames=("act", "block_b", "interpret"))
def int8_matmul(x, w_q, scale, b, *, act: str = "none",
                block_b: int = 128, interpret: bool = False):
    """``x @ dequant(w_q, scale) + b`` with the dequant fused into the
    matmul tile.  x: (B, d) fp32; w_q: (d, c) int8; scale/b: (c,) fp32;
    ``act='selu'`` fuses the hidden-layer activation.  Inference-only
    (the quantized path never trains), so no custom VJP."""
    if act not in ("none", "selu"):
        raise ValueError(f"int8_matmul: unknown act {act!r}")
    if w_q.dtype != jnp.int8:
        raise TypeError(f"int8_matmul: w_q must be int8, got {w_q.dtype}")
    B, d = x.shape
    c = w_q.shape[1]
    bb = min(int(block_b), B) if B else 1
    pad = (-B) % bb
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nt = (B + pad) // bb
    full = lambda shp: pl.BlockSpec(shp, lambda i: (0,) * len(shp))
    out = pl.pallas_call(
        functools.partial(_int8_kernel, act=act),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i: (i, 0)),
            full((d, c)), full((c,)), full((c,)),
        ],
        out_specs=pl.BlockSpec((bb, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nt * bb, c), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), w_q, scale.astype(jnp.float32),
      b.astype(jnp.float32))
    return out[:B]
