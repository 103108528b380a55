"""Streaming synthetic vertical partitions at scale — the million-row
workload of the mesh-sharded lane engine.

``make_scale_lanes`` builds an n-row x K-party vertical partition where
every party holds a correlated nonlinear view of the SAME rows (the
latent-factor recipe of :mod:`repro.data.synthetic`, shared latent ``z``
per row, per-party ``tanh`` feature views), sized so the single-device
host path cannot touch it.  Two properties make it a *scale* generator
rather than a bigger ``make_dataset``:

* **device-resident streaming**: rows are generated block-by-block inside
  one jitted kernel driven by ``jax.random`` — a ``(n, d)`` host numpy
  buffer never exists; blocks are concatenated on device and (optionally)
  placed row-sharded across a mesh's ``data`` axis as they are built;
* **lane-shaped output**: the return value is a list of
  ``training.LaneSpec`` (one per party x seed replicate, each with fresh
  encoder params and its own PRNG stream), i.e. exactly what
  ``training.train_lanes(..., mesh=...)`` consumes — parties ARE lanes.

Labels are not generated: the scale benchmark measures the g1
representation-learning stage (``masked_recon_loss``), which is where the
paper's local-compute claim lives; the probe stage is O(z_dim) and
irrelevant at this scale.

Features are approximately standardized by construction (unit-variance
latents through ``tanh`` of an O(1) mix plus scaled noise, then a fixed
analytic rescale) — exact per-column standardization would need a second
full pass over data that deliberately never sits in one buffer.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autoencoder as ae
from repro.core.training import LaneSpec


@partial(jax.jit, static_argnames=("n_rows", "n_latent", "n_features",
                                   "noise"))
def _party_block(kz, ke, mix, *, n_rows: int, n_latent: int,
                 n_features: int, noise: float):
    """One block of one party's rows, entirely on device: shared latents
    (``kz`` derived from the block index only, so every party's view of a
    block draws the SAME z) through the party's mixing matrix, saturating
    tanh, party-specific noise (``ke``), fixed analytic rescale to ~unit
    variance."""
    z = jax.random.normal(kz, (n_rows, n_latent))
    v = jnp.tanh(z @ mix)                      # var(tanh(N(0,~1))) ~ 0.4
    x = v + noise * jax.random.normal(ke, (n_rows, n_features))
    return (x / np.sqrt(0.4 + noise * noise)).astype(jnp.float32)


def _party_mix(n_latent: int, n_features: int, party: int = 0):
    """Party mixing matrix: each feature reads (mostly) one latent factor
    plus a weak second — the synthetic.make_dataset column recipe,
    vectorized; the party index rotates which latents a party observes, so
    parties hold genuinely different (but correlated) views."""
    mix = np.zeros((n_latent, n_features), np.float32)
    for j in range(n_features):
        mix[(j + party) % n_latent, j] = 1.3
        mix[(j * 5 + 1 + party) % n_latent, j] += 0.25
    return jnp.asarray(mix)


def make_scale_party(n_rows: int, *, n_features: int, n_latent: int = 8,
                     party: int = 0, seed: int = 0, noise: float = 0.5,
                     block_rows: int = 1 << 17, mesh=None) -> jax.Array:
    """One party's ``(n_rows, n_features)`` feature block, streamed on
    device in ``block_rows`` chunks.  Block b's latent key depends only on
    ``(seed, b)`` — NOT on the party — so all parties of one scenario see
    the same latent z per row: a genuine vertical partition.  With a
    ``mesh`` whose ``data`` axis has several devices and divides
    ``n_rows``, the finished array is placed row-sharded across it (a
    one-device data axis leaves it where it was made, rather than
    replicating it over the lane axis)."""
    mix = _party_mix(n_latent, n_features, party)
    blocks = []
    done = 0
    b = 0
    while done < n_rows:
        rows = min(block_rows, n_rows - done)
        kz = jax.random.fold_in(jax.random.PRNGKey(seed), b)
        ke = jax.random.fold_in(kz, party + 1)   # party-specific noise
        blocks.append(_party_block(
            kz, ke, mix, n_rows=rows, n_latent=n_latent,
            n_features=n_features, noise=noise))
        done += rows
        b += 1
    x = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)
    if mesh is not None and "data" in mesh.axis_names:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if sizes["data"] > 1 and n_rows % sizes["data"] == 0:
            x = jax.device_put(x, NamedSharding(mesh, P("data")))
    return x


def make_scale_lanes(n_rows: int, n_parties: int, *, n_features: int = 16,
                     n_latent: int = 8, widths: Optional[list] = None,
                     seeds=(0,), noise: float = 0.5,
                     block_rows: int = 1 << 17,
                     mesh=None) -> List[LaneSpec]:
    """The benchmark workload: ``n_parties * len(seeds)`` equal-shape
    lanes, one per (party, seed replicate).  Each seed replicate re-draws
    the scenario (fresh latents, fresh encoder inits, its own train/val
    split and epoch perms via ``LaneSpec.seed``); within one seed, all
    parties share latents per row.  Feed the result straight to
    ``training.train_lanes(lanes, ae.masked_recon_loss, mesh=...)``."""
    widths = list(widths) if widths is not None else [n_features, 32, 64]
    if widths[0] != n_features:
        raise ValueError(f"widths[0] ({widths[0]}) must equal n_features "
                         f"({n_features})")
    lanes = []
    for si, s in enumerate(seeds):
        for party in range(n_parties):
            x = make_scale_party(n_rows, n_features=n_features,
                                 n_latent=n_latent, party=party, seed=int(s),
                                 noise=noise, block_rows=block_rows,
                                 mesh=mesh)
            params = ae.init_autoencoder(
                jax.random.fold_in(jax.random.PRNGKey(int(s) + 7001), party),
                widths)
            lanes.append(LaneSpec(params, {"x": x},
                                  seed=int(s) * 100 + party))
    return lanes
