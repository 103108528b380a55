"""Seeded generators of the benchmark's inputs.

Copies of the program's own generators, kept here so that a later change
to the program cannot move the yardstick: the synthetic tabular recipe
(``data/synthetic.make_dataset``), the vertical partition
(``data/vertical.make_scenario``), the device-resident party block of the
scale grid (``data/scale._party_block``), the mixed request stream
(``serve/vfl.make_request_stream``) and Poisson arrivals
(``serve/runtime.poisson_arrivals``).  ``tests/test_bench_gen.py`` pins
each with a seeded checksum.
"""
from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

# the published shapes of the paper's tabular datasets (App. A)
DATASETS = {
    "mimic3": dict(n=20000, d=15, n_classes=4, latent=6, noise=0.7),
    "credit": dict(n=20000, d=23, n_classes=2, latent=6, noise=0.9),
}


def derive(seed: int, *labels) -> int:
    """A 31-bit seed drawn from the run's ``--seed`` (any size) and labels,
    so that every generator gets its own stream."""
    ss = np.random.SeedSequence([int(seed) % (1 << 63), *map(int, labels)])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def make_dataset(name: str, seed: int) -> dict:
    """A latent-factor stand-in at the dataset's published shape: labels
    from linear and quadratic latent terms, each feature a saturating view
    of mostly one latent, standardized columns, unique record ids."""
    spec = DATASETS[name]
    rng = np.random.RandomState(seed)
    n, d, c, r = spec["n"], spec["d"], spec["n_classes"], spec["latent"]
    z = rng.randn(n, r)
    wy = rng.randn(r, c) * 1.0
    wy2 = rng.randn(r, c) * 1.2
    wyx = rng.randn(r, c) * 0.8
    logits = (z @ wy + (z * z - 1.0) @ wy2 + (z * np.roll(z, 1, axis=1)) @ wyx
              + rng.randn(n, c) * 0.5)
    y = np.argmax(logits, axis=1)
    x = np.empty((n, d))
    for j in range(d):
        v = 1.3 * z[:, j % r] + 0.25 * z[:, (j * 5 + 1) % r]
        x[:, j] = np.tanh(v + 0.3 * rng.randn())
    x = x + rng.randn(n, d) * spec["noise"] * 0.6
    x = (x - x.mean(0)) / (x.std(0) + 1e-8)
    ids = rng.permutation(10 * n)[:n].astype(np.int64)
    return {"name": name, "x": x.astype(np.float32), "y": y.astype(np.int64),
            "n_classes": c, "ids": ids}


def make_scenario(ds: dict, *, n_active_features: int, n_aligned: int,
                  seed: int) -> dict:
    """Vertical split: the active party gets ``n_active_features`` columns
    and the labels, the passive party the rest; ``n_aligned`` ids are held
    by both and the other rows are split evenly between them."""
    rng = np.random.RandomState(seed + 1000)
    cols = rng.permutation(ds["x"].shape[1])
    a_cols = np.sort(cols[:n_active_features])
    p_cols = np.sort(cols[n_active_features:])
    perm = rng.permutation(len(ds["x"]))
    aligned, rest = perm[:n_aligned], perm[n_aligned:]
    half = len(rest) // 2
    a_rows = np.concatenate([aligned, rest[:half]])
    p_rows = np.concatenate([aligned, rest[half:]])
    return {"xa": ds["x"][a_rows][:, a_cols], "ids_a": ds["ids"][a_rows],
            "ya": ds["y"][a_rows], "xp": ds["x"][p_rows][:, p_cols],
            "ids_p": ds["ids"][p_rows], "n_classes": ds["n_classes"],
            "n_aligned": n_aligned, "a_cols": a_cols, "p_cols": p_cols}


def party_mix(n_latent: int, n_features: int, party: int) -> np.ndarray:
    """Each feature reads mostly one latent and weakly a second; the party
    index rotates which latents a party sees."""
    mix = np.zeros((n_latent, n_features), np.float32)
    for j in range(n_features):
        mix[(j + party) % n_latent, j] = 1.3
        mix[(j * 5 + 1 + party) % n_latent, j] += 0.25
    return mix


@lru_cache(maxsize=None)
def _party_block_fn():
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnames=("n_rows", "n_latent", "n_features",
                                       "noise"))
    def block(kz, ke, mix, *, n_rows, n_latent, n_features, noise):
        z = jax.random.normal(kz, (n_rows, n_latent))
        v = jnp.tanh(z @ mix)
        x = v + noise * jax.random.normal(ke, (n_rows, n_features))
        return (x / np.sqrt(0.4 + noise * noise)).astype(jnp.float32)

    return block


def make_party(n_rows: int, *, n_features: int, n_latent: int, party: int,
               seed: int, noise: float, block_rows: int = 1 << 17, device=None):
    """One party's ``(n_rows, n_features)`` rows, built block by block on
    ``device``: block b's latents depend on ``(seed, b)`` only, so every
    party of a scenario sees the same latent row."""
    import jax
    import jax.numpy as jnp

    block_fn = _party_block_fn()
    mix = jnp.asarray(party_mix(n_latent, n_features, party))
    if device is not None:
        mix = jax.device_put(mix, device)
    blocks, done, b = [], 0, 0
    while done < n_rows:
        rows = min(block_rows, n_rows - done)
        kz = jax.random.fold_in(jax.random.PRNGKey(seed), b)
        ke = jax.random.fold_in(kz, party + 1)
        if device is not None:
            kz, ke = jax.device_put((kz, ke), device)
        blocks.append(block_fn(kz, ke, mix, n_rows=rows, n_latent=n_latent,
                               n_features=n_features, noise=noise))
        done += rows
        b += 1
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)


def make_request_stream(x_pool, ids_pool, n_requests: int, *, seed: int,
                        max_rows: int, p_known: float) -> list:
    """The mixed request stream, ``[(x, ids), ...]``: sizes uniform in
    [1, max_rows], rows drawn from the pool, each row's id kept with
    probability ``p_known`` or replaced by a negative id no cache holds."""
    rng = np.random.RandomState(seed)
    x_pool = np.asarray(x_pool, np.float32)
    ids_pool = np.asarray(ids_pool, np.int64)
    reqs = []
    for _ in range(n_requests):
        n = int(rng.randint(1, max_rows + 1))
        rows = rng.randint(0, len(x_pool), n)
        ids = ids_pool[rows].copy()
        unknown = rng.rand(n) >= p_known
        ids[unknown] = -1 - rng.randint(0, 1 << 30, int(unknown.sum()))
        reqs.append((x_pool[rows], ids))
    return reqs


def poisson_arrivals(n: int, rate_rps: float, *, seed: int) -> np.ndarray:
    """n arrival times (ms) of a Poisson process at ``rate_rps``."""
    rng = np.random.RandomState(seed)
    return np.cumsum(rng.exponential(1000.0 / rate_rps, size=n))
