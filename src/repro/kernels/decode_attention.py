"""Pallas TPU kernel for one-token decode attention against a (possibly
sliding-window) KV cache — the decode_32k/long_500k hot spot.

Per (batch*head) grid cell the query is a single row; the cache streams
through VMEM in ``block_w`` slot tiles with online-softmax accumulation, so
the (W,) score vector never reaches HBM and invalid slots (slot_pos < 0,
future, or out-of-window) are masked inside the tile.  The GQA expansion
happens at the wrapper level (kv heads broadcast to q heads), matching
``models/attention.decode_attention`` semantics exactly.

VMEM per step at defaults (block_w=512, hd=128): k/v tiles 2*128KiB +
q 0.5KiB + scalars — trivially resident; the cache stream is the whole
traffic, which is the roofline lower bound for decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, sp_ref, pos_ref, o_ref, m_ref, l_ref,
            acc_ref, *, scale: float, block_w: int, window: int, n_w: int):
    wj = pl.program_id(1)

    @pl.when(wj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)               # (1, hd)
    k = k_ref[0].astype(jnp.float32)                 # (bw, hd)
    v = v_ref[0].astype(jnp.float32)
    sp = sp_ref[...]                                 # (bw,) int32 slot pos
    pos = pos_ref[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)[0] * scale
    ok = (sp >= 0) & (sp <= pos)
    if window:
        ok &= sp > pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[0]
    m_cur = jnp.maximum(m_prev, jnp.max(s))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(ok, jnp.exp(s - m_cur), 0.0)       # (bw,)
    l_ref[0] = l_ref[0] * alpha + jnp.sum(p)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p[None, :], v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[0] = m_cur

    @pl.when(wj == n_w - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[0], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block_w",
                                             "interpret"))
def decode_attention(q, k, v, slot_pos, pos, *, window: int = 0,
                     block_w: int = 512, interpret: bool = False):
    """q: (BH, hd) one query row per batch*head; k/v: (BH, W, hd);
    slot_pos: (W,) int32; pos: scalar int32. Returns (BH, hd)."""
    BH, hd = q.shape
    W = k.shape[1]
    bw = min(block_w, W)
    assert W % bw == 0
    n_w = W // bw
    kern = functools.partial(_kernel, scale=1.0 / np.sqrt(hd), block_w=bw,
                             window=window, n_w=n_w)
    return pl.pallas_call(
        kern,
        grid=(BH, n_w),
        in_specs=[
            pl.BlockSpec((1, hd), lambda b, j: (b, 0)),
            pl.BlockSpec((1, bw, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bw, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((bw,), lambda b, j: (j,)),
            pl.BlockSpec((1,), lambda b, j: (0,)),
        ],
        out_specs=pl.BlockSpec((1, hd), lambda b, j: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, slot_pos, pos[None].astype(jnp.int32))
