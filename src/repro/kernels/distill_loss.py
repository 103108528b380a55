"""Fused APC-VFL composite loss (paper Eq. 5) as a Pallas TPU kernel.

One VMEM-resident pass computes, per row,
    rec_i  = mean_d (x_i - x_hat_i)^2
    dis_i  = mean_m |z_i - zt_i|^p        (p = 2 for MSE, 1 for MAE)
    out_i  = rec_i + lam * aligned_i * dis_i
fusing four elementwise streams + two row reductions that XLA would
otherwise materialize separately in HBM.  Batch rows are tiled 128 at a
time (8-sublane x fp32 tiles); feature dims ride whole in VMEM (tabular
dims here are <= 1024: ~1.5MiB per tile at the defaults).

The Eq. 5 backward is closed-form, so ``fused_distill_rows`` carries a
``jax.custom_vjp`` whose backward is a second fused Pallas kernel (same
tiling): for row cotangents g_i,
    d x_i    =  g_i * 2 (x_i - xh_i) / D          (d xh_i = -d x_i)
    d z_i    =  g_i * lam * a_i * p |z_i-zt_i|^{p-1} sgn(z_i-zt_i) / M
                                                  (d zt_i = -d z_i)
    d a_i    =  g_i * lam * dis_i
This is what lets ``use_kernel=True`` train under ``jax.value_and_grad``
in the scan engine (the raw ``pallas_call`` has no VJP rule).

Per-row vectors (mask, losses, their cotangents) cross the kernel
boundary as ``(rows, 1)`` columns: Mosaic requires each block's last two
dimensions to be (8, 128)-aligned or to span the array's, and a 1-D
``(block_b,)`` block stops meeting that once ``jax.vmap`` prepends the
lane axis of the lane engine.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, xh_ref, z_ref, zt_ref, m_ref, o_ref, *, lam: float,
            kind: str):
    x = x_ref[...].astype(jnp.float32)
    xh = xh_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    zt = zt_ref[...].astype(jnp.float32)
    mask = m_ref[...].astype(jnp.float32)                       # (bb, 1)
    rec = jnp.mean(jnp.square(x - xh), axis=-1, keepdims=True)
    diff = z - zt
    dis = (jnp.mean(jnp.abs(diff), axis=-1, keepdims=True) if kind == "mae"
           else jnp.mean(jnp.square(diff), axis=-1, keepdims=True))
    o_ref[...] = rec + lam * mask * dis


def _bwd_kernel(g_ref, x_ref, xh_ref, z_ref, zt_ref, m_ref,
                dx_ref, dz_ref, dm_ref, *, lam: float, kind: str):
    g = g_ref[...].astype(jnp.float32)                          # (bb, 1)
    x = x_ref[...].astype(jnp.float32)
    xh = xh_ref[...].astype(jnp.float32)
    z = z_ref[...].astype(jnp.float32)
    zt = zt_ref[...].astype(jnp.float32)
    mask = m_ref[...].astype(jnp.float32)                       # (bb, 1)
    D = x.shape[-1]
    M = z.shape[-1]
    diff = z - zt
    dx_ref[...] = (g * (2.0 / D)) * (x - xh)
    if kind == "mae":
        dis = jnp.mean(jnp.abs(diff), axis=-1, keepdims=True)
        ddis = jnp.sign(diff) / M
    else:
        dis = jnp.mean(jnp.square(diff), axis=-1, keepdims=True)
        ddis = 2.0 * diff / M
    dz_ref[...] = (g * lam * mask) * ddis
    dm_ref[...] = g * lam * dis


def _pad_rows(arrs, pad: int):
    if not pad:
        return arrs
    padf = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    return tuple(padf(a) for a in arrs)


def _rows_fwd_call(x, x_hat, z, z_t, mask, lam, kind, block_b, interpret):
    B, D = x.shape
    M = z.shape[1]
    pad = (-B) % block_b
    x, x_hat, z, z_t, mask = _pad_rows((x, x_hat, z, z_t, mask[:, None]),
                                       pad)
    Bp = B + pad
    out = pl.pallas_call(
        functools.partial(_kernel, lam=lam, kind=kind),
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, D), lambda i: (i, 0)),
            pl.BlockSpec((block_b, D), lambda i: (i, 0)),
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        interpret=interpret,
    )(x, x_hat, z, z_t, mask)
    return out[:B, 0]


def _rows_bwd_call(g, x, x_hat, z, z_t, mask, lam, kind, block_b, interpret):
    B, D = x.shape
    M = z.shape[1]
    pad = (-B) % block_b
    g, x, x_hat, z, z_t, mask = _pad_rows(
        (g[:, None], x, x_hat, z, z_t, mask[:, None]), pad)
    Bp = B + pad
    dx, dz, dm = pl.pallas_call(
        functools.partial(_bwd_kernel, lam=lam, kind=kind),
        grid=(Bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, D), lambda i: (i, 0)),
            pl.BlockSpec((block_b, D), lambda i: (i, 0)),
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, D), lambda i: (i, 0)),
            pl.BlockSpec((block_b, M), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, D), jnp.float32),
            jax.ShapeDtypeStruct((Bp, M), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(g, x, x_hat, z, z_t, mask)
    return dx[:B], dz[:B], dm[:B, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _rows(x, x_hat, z, z_t, mask, lam, kind, block_b, interpret):
    return _rows_fwd_call(x, x_hat, z, z_t, mask, lam, kind, block_b,
                          interpret)


def _rows_fwd(x, x_hat, z, z_t, mask, lam, kind, block_b, interpret):
    out = _rows_fwd_call(x, x_hat, z, z_t, mask, lam, kind, block_b,
                         interpret)
    return out, (x, x_hat, z, z_t, mask)


def _rows_bwd(lam, kind, block_b, interpret, res, g):
    x, x_hat, z, z_t, mask = res
    dx, dz, dm = _rows_bwd_call(g, x, x_hat, z, z_t, mask, lam, kind,
                                block_b, interpret)
    cast = lambda d, ref: d.astype(ref.dtype)
    return (cast(dx, x), cast(-dx, x_hat), cast(dz, z), cast(-dz, z_t),
            cast(dm, mask))


_rows.defvjp(_rows_fwd, _rows_bwd)


@functools.partial(jax.jit, static_argnames=("lam", "kind", "block_b",
                                             "interpret"))
def fused_distill_rows(x, x_hat, z, z_t, mask, *, lam: float = 0.01,
                       kind: str = "mse", block_b: int = 128,
                       interpret: bool = False):
    """Per-row Eq. 5 losses. x/x_hat: (B, D); z/z_t: (B, M); mask: (B,).
    Differentiable (closed-form custom VJP, module docstring)."""
    return _rows(x, x_hat, z, z_t, mask, float(lam), str(kind),
                 int(block_b), bool(interpret))
