"""Fused weighted softmax-CE probe step (the k-fold CV logreg) as a
Pallas kernel.

The CV probe (``classifier``) runs 300 Adam steps per fold whose body is
two GEMV-shaped matmuls (``x @ w`` then ``x.T @ g``) plus a softmax —
memory-bound on re-reading ``x``.  This kernel fuses the whole gradient
step: one pass over a batch tile produces the weighted-CE loss partial,
``dW`` partial and ``db`` partial together, so ``x`` is read once per
step instead of once per op.

Fold/seed lanes enter through ``jax.vmap`` exactly as in
``kernels.lane_mlp``: the ``pallas_call`` batching rule prepends the
vmapped axis as the OUTERMOST grid dimension, so all k folds x S seeds
run as rows of one lane-major (lanes, batch_tiles) grid.  The full-row
weight formulation makes that possible — every fold sees the SAME
``x``/``y`` and differs only in its 0/1 row-weight vector (zero for the
fold's own test rows and padding), so dead rows are exactly inert.

Per-tile partials (loss, dW, db) are written on the leading grid axis
and reduced outside the kernel — batching-safe by construction, like the
lane-MLP backward.  Row weights arrive PRE-normalized (the wrapper
divides by ``max(sum(rw), 1)``) so tiles need no global reduction; the
L2 term is added outside.  Row vectors (labels, weights, bias) enter as
2-D columns/rows and partials leave as ``(tiles, 1, width)``, so every
block's last two dimensions are (8, 128)-aligned or span the array's, as
Mosaic requires with or without the vmapped fold axis.  Matches
``kernels.ref.probe_grad_ref``, i.e. the autodiff gradient of
``classifier._weighted_logreg_loss``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _probe_kernel(x_ref, y_ref, rwn_ref, w_ref, b_ref,
                  loss_ref, dw_ref, db_ref):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    rwn = rwn_ref[...].astype(jnp.float32)                     # (bb, 1)
    logits = jnp.dot(x, w, preferred_element_type=jnp.float32) \
        + b_ref[...].astype(jnp.float32)
    # stable logsumexp + softmax sharing one max/exp evaluation
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    se = jnp.sum(e, axis=-1, keepdims=True)
    lse = jnp.log(se) + m
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
              == y_ref[...]).astype(jnp.float32)
    gold = jnp.sum(logits * onehot, axis=-1, keepdims=True)
    loss_ref[0] = jnp.sum((lse - gold) * rwn, axis=0, keepdims=True)
    g = (e / se - onehot) * rwn
    dw_ref[0] = jnp.dot(x.T, g, preferred_element_type=jnp.float32)
    db_ref[0] = jnp.sum(g, axis=0, keepdims=True)


def _probe_call(x, y, rwn, w, b, block_b: int, interpret: bool):
    B, d = x.shape
    c = w.shape[1]
    pad = (-B) % block_b
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        y = jnp.pad(y, ((0, pad),))
        rwn = jnp.pad(rwn, ((0, pad),))  # zero weight -> padded rows inert
    Bp = B + pad
    nt = Bp // block_b
    full = lambda shp: pl.BlockSpec(shp, lambda i: (0,) * len(shp))
    lossp, dwp, dbp = pl.pallas_call(
        _probe_kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            full((d, c)), full((1, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, d, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, c), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nt, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((nt, d, c), jnp.float32),
            jax.ShapeDtypeStruct((nt, 1, c), jnp.float32),
        ],
        interpret=interpret,
    )(x, y[:, None], rwn[:, None], w, b.reshape(1, c))
    return jnp.sum(lossp), jnp.sum(dwp, axis=0), jnp.sum(dbp, axis=(0, 1))


@functools.partial(jax.jit, static_argnames=("l2", "block_b", "interpret"))
def probe_grad_step(w, b, x, y, rw, *, l2: float = 1e-4,
                    block_b: int = 128, interpret: bool = False):
    """One fused probe gradient step: returns (loss, dW, db).

    w: (d, C); b: (C,); x: (n, d); y: (n,) int labels; rw: (n,)
    row weights (0 disables a row exactly).  Semantics pinned by
    ``kernels.ref.probe_grad_ref``.  Fold/seed lanes via ``jax.vmap``
    with ``in_axes=(0, 0, None, None, 0)``."""
    denom = jnp.maximum(jnp.sum(rw), 1.0)
    rwn = (rw / denom).astype(jnp.float32)
    loss, dw, db = _probe_call(x, y.astype(jnp.int32), rwn, w, b,
                               int(block_b), bool(interpret))
    loss = loss + l2 * jnp.sum(jnp.square(w))
    return loss, dw + 2.0 * l2 * w.astype(jnp.float32), db
