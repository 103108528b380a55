"""Paper Eq. 5: composite reconstruction + masked distillation loss.

    L_total(x_i) = L_enc-dec(x_i) + lambda * L_distill(x_i)   if x_i aligned
                 = L_enc-dec(x_i)                              otherwise

L_distill is MSE or MAE between the teacher joint latent z_A_i and the
student latent g3(x_i).  The batch carries z_A rows (zeros where unaligned)
and an ``aligned`` {0,1} mask; masking reproduces the per-sample case split.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import autoencoder as ae


def distill_loss(params: dict, batch: dict, *, lam: float = 0.01,
                 kind: str = "mse", use_kernel: bool = False) -> jax.Array:
    x, z_t, mask = batch["x"], batch["z_teacher"], batch["aligned"]
    if use_kernel:
        from repro.kernels import ops as kops
        z = ae.fused_encode(params, x)
        x_hat = ae.fused_mlp_apply(params["dec"], z)
        return kops.fused_distill_loss(x, x_hat, z, z_t, mask, lam=lam,
                                       kind=kind)
    z = ae.encode(params, x)
    x_hat = ae.mlp_apply(params["dec"], z)
    rec = jnp.mean(jnp.square(x - x_hat), axis=-1)               # (B,)
    diff = z - z_t
    if kind == "mae":
        dis = jnp.mean(jnp.abs(diff), axis=-1)
    else:
        dis = jnp.mean(jnp.square(diff), axis=-1)
    per_row = rec + lam * dis * mask.astype(rec.dtype)
    return jnp.mean(per_row)


def make_loss(lam: float = 0.01, kind: str = "mse", use_kernel: bool = False):
    def loss(params, batch):
        return distill_loss(params, batch, lam=lam, kind=kind,
                            use_kernel=use_kernel)
    # semantic identity: every closure with the same hyperparameters shares
    # one compiled lane engine (training.get_lanes_fit_engine) instead of
    # re-tracing per make_loss() call
    loss.cache_key = ("repro.core.distill.make_loss", float(lam), str(kind),
                      bool(use_kernel))
    return loss


def make_lanes_loss(lam: float = 0.01, kind: str = "mse",
                    use_kernel: bool = False):
    """Eq. 5 for replica-lane batches (``training.train_lanes``): consumes
    the engine's ``mask`` (real-feature columns) and ``row_w`` (real-row
    weights), so g3 lanes of different row/feature shapes can share one
    vmapped scan.  With 0/1 weights and no padding this equals
    ``make_loss(lam, kind)`` exactly (the weighted means reduce to plain
    means).  Lanes must share the latent width (true for every Table-3
    architecture: M3 = 256) — the latent axis is never padded.

    ``use_kernel=True`` computes the per-row Eq. 5 terms through the fused
    Pallas kernel (trainable since it grew its closed-form custom VJP).
    The kernel averages over all D feature columns, so the 0/1 feature
    mask is folded in by pre-masking x / x_hat and rescaling by
    sqrt(D / sum(mask)) — exact for 0/1 masks, a no-op for unpadded
    lanes."""
    def loss(params, batch):
        x, z_t, al = batch["x"], batch["z_teacher"], batch["aligned"]
        fm, rw = batch["mask"], batch["row_w"]
        if use_kernel:
            from repro.kernels import ops as kops
            z = ae.fused_encode(params, x)
            x_hat = ae.fused_mlp_apply(params["dec"], z)
            s = jnp.sqrt(x.shape[-1] / jnp.maximum(jnp.sum(fm), 1.0))
            per_row = kops.fused_distill_rows(x * fm * s, x_hat * fm * s,
                                              z, z_t, al, lam=lam, kind=kind)
        else:
            z = ae.encode(params, x)
            x_hat = ae.mlp_apply(params["dec"], z)
            se = jnp.square(x - x_hat) * fm
            rec = jnp.sum(se, axis=-1) / jnp.maximum(jnp.sum(fm), 1.0)  # (B,)
            diff = z - z_t
            if kind == "mae":
                dis = jnp.mean(jnp.abs(diff), axis=-1)
            else:
                dis = jnp.mean(jnp.square(diff), axis=-1)
            per_row = rec + lam * dis * al.astype(rec.dtype)
        return jnp.sum(per_row * rw) / jnp.maximum(jnp.sum(rw), 1.0)
    loss.cache_key = ("repro.core.distill.make_lanes_loss", float(lam),
                      str(kind), bool(use_kernel))
    return loss
