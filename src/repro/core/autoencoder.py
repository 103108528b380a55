"""Symmetric MLP autoencoders (paper Table 3, SELU activations).

Encoder layer widths are given per Table 3; the decoder mirrors them in
reverse ("all autoencoders considered in APC-VFL are symmetric").  The
linear latent layer (no activation on the last encoder layer) follows the
overcomplete-autoencoder usage in the paper.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


def table3_encoder(role: str, n_features: int) -> list:
    """Paper Table 3 widths. role: g1_active|g1_passive|g2|g3."""
    return {
        "g1_active": [n_features, 64, 128],
        "g1_passive": [n_features, 128, 256],
        "g2": [n_features, 256, 256],
        "g3": [n_features, 256, 256],
    }[role]


def init_mlp(key, widths: Sequence[int]) -> dict:
    params = {}
    keys = jax.random.split(key, len(widths) - 1)
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        # LeCun normal — the recommended init for SELU networks
        params[f"w{i}"] = jax.random.normal(keys[i], (a, b)) / np.sqrt(a)
        params[f"b{i}"] = jnp.zeros((b,))
    return params


def mlp_apply(params: dict, x: jax.Array, *, final_act: bool = False) -> jax.Array:
    n = len([k for k in params if k.startswith("w")])
    for i in range(n):
        x = x @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1 or final_act:
            x = jax.nn.selu(x)
    return x


def init_autoencoder(key, enc_widths: Sequence[int]) -> dict:
    k1, k2 = jax.random.split(key)
    return {"enc": init_mlp(k1, list(enc_widths)),
            "dec": init_mlp(k2, list(enc_widths)[::-1])}


def encode(params: dict, x: jax.Array) -> jax.Array:
    return mlp_apply(params["enc"], x)


def reconstruct(params: dict, x: jax.Array) -> jax.Array:
    return mlp_apply(params["dec"], encode(params, x))


def fused_mlp_apply(params: dict, x: jax.Array, *,
                    final_act: bool = False) -> jax.Array:
    """``mlp_apply`` through the fused Pallas kernel when the MLP is the
    2-layer Table-3 shape (one fused fwd pass + closed-form VJP);
    arbitrary-depth MLPs fall back to the jnp layer loop.  The branch is
    on pytree STRUCTURE (layer count), a trace-time constant."""
    if len([k for k in params if k.startswith("w")]) != 2:
        return mlp_apply(params, x, final_act=final_act)
    from repro.kernels import ops as kops
    return kops.fused_mlp2(x, params["w0"], params["b0"], params["w1"],
                           params["b1"], final_act=final_act)


def fused_encode(params: dict, x: jax.Array) -> jax.Array:
    return fused_mlp_apply(params["enc"], x)


def fused_reconstruct(params: dict, x: jax.Array) -> jax.Array:
    return fused_mlp_apply(params["dec"], fused_encode(params, x))


def recon_loss(params: dict, batch: dict) -> jax.Array:
    x = batch["x"]
    return jnp.mean(jnp.square(x - reconstruct(params, x)))


def masked_recon_loss(params: dict, batch: dict) -> jax.Array:
    """``recon_loss`` over the padded-stack batches of
    ``training.train_lanes``: ``mask`` (D,) selects the party's real feature
    columns, ``row_w`` (B,) its real rows.  With no padding this equals
    ``recon_loss`` exactly (mean over real entries)."""
    x, fm, rw = batch["x"], batch["mask"], batch["row_w"]
    se = jnp.square(x - reconstruct(params, x)) * fm
    per_row = jnp.sum(se, axis=-1) / jnp.maximum(jnp.sum(fm), 1.0)
    return jnp.sum(per_row * rw) / jnp.maximum(jnp.sum(rw), 1.0)


def make_recon_loss(use_kernel: bool = False):
    """``recon_loss`` with the reconstruction routed through the fused
    lane-MLP kernel (``kernels.lane_mlp``) when ``use_kernel=True`` —
    identical math (kernel grads are exact vs the jnp path), one fused
    pass per MLP instead of a per-layer HBM round-trip."""
    if not use_kernel:
        return recon_loss

    def loss(params: dict, batch: dict) -> jax.Array:
        x = batch["x"]
        return jnp.mean(jnp.square(x - fused_reconstruct(params, x)))

    loss.cache_key = ("repro.core.autoencoder.make_recon_loss", True)
    return loss


def make_masked_recon_loss(use_kernel: bool = False):
    """``masked_recon_loss`` with a fused-kernel reconstruction path —
    the lane-engine (``train_lanes``) variant of ``make_recon_loss``."""
    if not use_kernel:
        return masked_recon_loss

    def loss(params: dict, batch: dict) -> jax.Array:
        x, fm, rw = batch["x"], batch["mask"], batch["row_w"]
        se = jnp.square(x - fused_reconstruct(params, x)) * fm
        per_row = jnp.sum(se, axis=-1) / jnp.maximum(jnp.sum(fm), 1.0)
        return jnp.sum(per_row * rw) / jnp.maximum(jnp.sum(rw), 1.0)

    loss.cache_key = ("repro.core.autoencoder.make_masked_recon_loss", True)
    return loss
