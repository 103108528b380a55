"""Pallas TPU flash attention (causal / sliding-window) with online softmax.

Grid: (batch*heads, q_blocks, kv_blocks); the kv dimension is the innermost
(sequential, "arbitrary") axis — fp32 running max / denominator / output
accumulator live in VMEM scratch across kv steps.  Block sizes default to
128x128 (MXU tile aligned); the head dim rides whole in VMEM.

VMEM budget per step (defaults, hd=128, fp32 scratch):
  q/k/v blocks 3 * 128*128*2B = 96KiB, acc 128*128*4B = 64KiB,
  m/l 2*128*4B = 1KiB  -> ~161KiB of ~16MiB VMEM: safely resident, leaving
room for double-buffered HBM->VMEM pipelining of the k/v streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, block_q: int, block_k: int, causal: bool,
                 window: int, n_kv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                       # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        ok &= cols <= rows
    if window:
        ok &= (rows - cols) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                    # (bq,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    p = jnp.where(ok, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(kj == n_kv - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q/k/v: (B, H, S, hd) -> (B, H, S, hd)."""
    B, H, S, hd = q.shape
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)

    kern = functools.partial(
        _attn_kernel, scale=1.0 / np.sqrt(hd), block_q=block_q,
        block_k=block_k, causal=causal, window=window, n_kv=nk)
    out = pl.pallas_call(
        kern,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),        # running max
            pltpu.VMEM((block_q,), jnp.float32),        # denominator
            pltpu.VMEM((block_q, hd), jnp.float32),     # output acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
