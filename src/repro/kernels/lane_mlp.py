"""Lane-blocked fused 2-layer MLP step (paper Table 3 encoders/decoders)
as a Pallas kernel pair.

``train_lanes`` spends its time on stacks of small per-lane matmuls —
``selu(x @ w0 + b0) @ w1 + b1`` per lane per batch tile — which XLA
schedules as separate HBM round-trips per layer.  The forward kernel here
keeps one batch tile plus both weight blocks VMEM-resident and emits the
output AND both pre-activations in a single pass; the backward is a
second fused kernel implementing the closed-form chain rule, so the pair
carries a ``jax.custom_vjp`` and trains under ``jax.value_and_grad``
inside the scan engine (a raw ``pallas_call`` has no VJP rule).

Lane blocking comes from the ``pallas_call`` batching rule: the lane
engine evaluates losses under ``jax.vmap``, which prepends the lane axis
as the OUTERMOST grid dimension — the compiled kernel runs on a
(lanes, batch_tiles) lane-major grid with each lane's weight block
resident for its row of tiles.  ``fused_lane_mlp2`` exposes that stacked
form directly (with a ``live`` mask rendering dead lanes inert) for
callers outside the engine and for the benches.

Backward, for upstream cotangent ``g`` (per tile; selu' is evaluated on
the saved pre-activations so gradients match autodiff exactly):

    g2  = g * selu'(a2)   if final_act else  g
    dW1 = selu(a1)^T g2          db1 = sum_rows g2
    g1  = (g2 W1^T) * selu'(a1)
    dW0 = x^T g1                 db0 = sum_rows g1
    dx  = g1 W0^T

Weight gradients are written as PER-TILE partials (leading grid axis)
and reduced outside the kernel: an in-kernel accumulator over
``pl.program_id`` would alias across the vmap-prepended lane axis,
per-tile partials are batching-safe by construction.

Mosaic requires each block's last two dimensions to be multiples of
(8, 128) or to equal the array's.  So biases enter as ``(1, width)`` rows
and bias partials leave as ``(tiles, 1, width)``: both stay legal when
``jax.vmap`` prepends the lane axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# jax.nn.selu constants: selu(x) = SCALE * where(x > 0, x, ALPHA*(e^x - 1))
_SELU_ALPHA = 1.6732632423543772848170429916717
_SELU_SCALE = 1.0507009873554804934193349852946


def _selu(a):
    # Mosaic lowers exp but not the fused e^a - 1 primitive; the two forms
    # differ by at most one float32 ulp of 1 (~1.2e-7) on the a <= 0 branch
    return _SELU_SCALE * jnp.where(a > 0, a, _SELU_ALPHA * (jnp.exp(a) - 1.0))


def _dselu(a):
    # exact derivative of the SELU that autodiff differentiates
    return _SELU_SCALE * jnp.where(a > 0, 1.0, _SELU_ALPHA * jnp.exp(a))


def _fwd_kernel(x_ref, w0_ref, b0_ref, w1_ref, b1_ref,
                out_ref, a1_ref, a2_ref, *, final_act: bool):
    x = x_ref[...].astype(jnp.float32)
    w0 = w0_ref[...].astype(jnp.float32)
    w1 = w1_ref[...].astype(jnp.float32)
    a1 = jnp.dot(x, w0, preferred_element_type=jnp.float32) \
        + b0_ref[...].astype(jnp.float32)
    h1 = _selu(a1)
    a2 = jnp.dot(h1, w1, preferred_element_type=jnp.float32) \
        + b1_ref[...].astype(jnp.float32)
    a1_ref[...] = a1
    a2_ref[...] = a2
    out_ref[...] = _selu(a2) if final_act else a2


def _bwd_kernel(g_ref, x_ref, a1_ref, a2_ref, w0_ref, w1_ref,
                dx_ref, dw0_ref, db0_ref, dw1_ref, db1_ref, *,
                final_act: bool):
    g = g_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    a1 = a1_ref[...].astype(jnp.float32)
    w0 = w0_ref[...].astype(jnp.float32)
    w1 = w1_ref[...].astype(jnp.float32)
    g2 = g * _dselu(a2_ref[...].astype(jnp.float32)) if final_act else g
    h1 = _selu(a1)
    dw1_ref[0] = jnp.dot(h1.T, g2, preferred_element_type=jnp.float32)
    db1_ref[0] = jnp.sum(g2, axis=0, keepdims=True)
    g1 = jnp.dot(g2, w1.T, preferred_element_type=jnp.float32) * _dselu(a1)
    dw0_ref[0] = jnp.dot(x.T, g1, preferred_element_type=jnp.float32)
    db0_ref[0] = jnp.sum(g1, axis=0, keepdims=True)
    dx_ref[...] = jnp.dot(g1, w0.T, preferred_element_type=jnp.float32)


def _pad_rows(arrs, pad: int):
    if not pad:
        return arrs
    padf = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return tuple(padf(a) for a in arrs)


def _fwd_call(x, w0, b0, w1, b1, final_act, block_b, interpret):
    B, din = x.shape
    h, dz = w0.shape[1], w1.shape[1]
    pad = (-B) % block_b
    (x,) = _pad_rows((x,), pad)
    Bp = B + pad
    full = lambda shp: pl.BlockSpec(shp, lambda i: (0,) * len(shp))
    out, a1, a2 = pl.pallas_call(
        functools.partial(_fwd_kernel, final_act=final_act),
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, din), lambda i: (i, 0)),
            full((din, h)), full((1, h)), full((h, dz)), full((1, dz)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, dz), lambda i: (i, 0)),
            pl.BlockSpec((block_b, h), lambda i: (i, 0)),
            pl.BlockSpec((block_b, dz), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, dz), jnp.float32),
            jax.ShapeDtypeStruct((Bp, h), jnp.float32),
            jax.ShapeDtypeStruct((Bp, dz), jnp.float32),
        ],
        interpret=interpret,
    )(x, w0, b0.reshape(1, h), w1, b1.reshape(1, dz))
    return out[:B], a1[:B], a2[:B]


def _bwd_call(g, x, a1, a2, w0, w1, final_act, block_b, interpret):
    B, din = x.shape
    h, dz = w0.shape[1], w1.shape[1]
    pad = (-B) % block_b
    g, x, a1, a2 = _pad_rows((g, x, a1, a2), pad)
    Bp = B + pad
    nt = Bp // block_b
    full = lambda shp: pl.BlockSpec(shp, lambda i: (0,) * len(shp))
    dx, dw0p, db0p, dw1p, db1p = pl.pallas_call(
        functools.partial(_bwd_kernel, final_act=final_act),
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, dz), lambda i: (i, 0)),
            pl.BlockSpec((block_b, din), lambda i: (i, 0)),
            pl.BlockSpec((block_b, h), lambda i: (i, 0)),
            pl.BlockSpec((block_b, dz), lambda i: (i, 0)),
            full((din, h)), full((h, dz)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, din), lambda i: (i, 0)),
            pl.BlockSpec((1, din, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, h, dz), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, dz), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, din), jnp.float32),
            jax.ShapeDtypeStruct((nt, din, h), jnp.float32),
            jax.ShapeDtypeStruct((nt, 1, h), jnp.float32),
            jax.ShapeDtypeStruct((nt, h, dz), jnp.float32),
            jax.ShapeDtypeStruct((nt, 1, dz), jnp.float32),
        ],
        interpret=interpret,
    )(g, x, a1, a2, w0, w1)
    return (dx[:B], jnp.sum(dw0p, axis=0), jnp.sum(db0p, axis=(0, 1)),
            jnp.sum(dw1p, axis=0), jnp.sum(db1p, axis=(0, 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _mlp2(x, w0, b0, w1, b1, final_act, block_b, interpret):
    out, _, _ = _fwd_call(x, w0, b0, w1, b1, final_act, block_b, interpret)
    return out


def _mlp2_fwd(x, w0, b0, w1, b1, final_act, block_b, interpret):
    out, a1, a2 = _fwd_call(x, w0, b0, w1, b1, final_act, block_b,
                            interpret)
    return out, (x, a1, a2, w0, b0, w1, b1)


def _mlp2_bwd(final_act, block_b, interpret, res, g):
    x, a1, a2, w0, b0, w1, b1 = res
    dx, dw0, db0, dw1, db1 = _bwd_call(g, x, a1, a2, w0, w1, final_act,
                                       block_b, interpret)
    cast = lambda d, ref: d.astype(ref.dtype)
    return (cast(dx, x), cast(dw0, w0), cast(db0, b0), cast(dw1, w1),
            cast(db1, b1))


_mlp2.defvjp(_mlp2_fwd, _mlp2_bwd)


@functools.partial(jax.jit, static_argnames=("final_act", "block_b",
                                             "interpret"))
def fused_mlp2(x, w0, b0, w1, b1, *, final_act: bool = False,
               block_b: int = 128, interpret: bool = False):
    """Fused ``selu(x @ w0 + b0) @ w1 + b1`` (optionally selu'd).
    x: (B, din); w0: (din, h); w1: (h, dz).  Differentiable (closed-form
    custom VJP, module docstring); lane axis enters via ``jax.vmap``."""
    return _mlp2(x, w0, b0, w1, b1, bool(final_act), int(block_b),
                 bool(interpret))


@functools.partial(jax.jit, static_argnames=("final_act", "block_b",
                                             "interpret"))
def fused_lane_mlp2(xs, w0s, b0s, w1s, b1s, live, *,
                    final_act: bool = False, block_b: int = 128,
                    interpret: bool = False):
    """Explicit lane-stacked form: xs (L, B, din), per-lane weight stacks,
    ``live`` (L,) 0/1 mask.  One lane-major (L, batch_tiles) kernel grid
    (vmap batching rule); dead lanes produce exact zeros."""
    out = jax.vmap(
        lambda x, w0, b0, w1, b1: _mlp2(x, w0, b0, w1, b1,
                                        bool(final_act), int(block_b),
                                        bool(interpret))
    )(xs, w0s, b0s, w1s, b1s)
    return out * live.astype(out.dtype)[:, None, None]
