"""Hardened one-shot exchange: composable ``ExchangeTransform``s applied
at APC-VFL's single latent exchange (``comm.exchange_array``).

APC-VFL's privacy surface is exactly one message: the passive party's
aligned-row latents.  Every defense therefore composes at that one point —
the transform runs at the SENDER, the channel accounts the transformed
wire bytes (per dtype — an int8 payload is 1 B/element, a sign payload
1 bit), and the active party only ever consumes what the transform
returns (the receiver's reconstruction).  Three building blocks:

* ``ClippedNoise`` — per-row norm clipping (L2 for the Gaussian
  mechanism, L1 for Laplace) followed by additive noise with scale
  ``sigma * clip`` (``clip=None`` skips clipping; sensitivity 1): the
  standard clipped-DP shape for representation perturbation.
* ``Quantize`` — per-feature symmetric quantization: ``"int8"`` (scale =
  absmax/127, 4x smaller wire) or ``"sign"`` (1-bit sign times the
  per-feature mean magnitude, ~32x smaller).
* ``Chain`` — stages applied in order at the sender; only the LAST
  stage's wire form is sent (earlier stages are local pre-processing),
  e.g. clip+noise THEN int8 = a DP'd quantized exchange.

``make_transform`` builds the chain from plain keyword knobs and returns
``None`` when every defense is off — so ``run_apcvfl_dp(sigma=0)`` takes
the exact ``exchange=None`` code path of ``run_apcvfl`` and is
bit-identical to it (pinned in ``tests/test_robustness.py``).

Noise randomness derives from ``fold_in(PRNGKey(seed), SALT)`` plus the
passive-link index — a pure function of the run's seed, never of lane
position — so the replicated lane paths reproduce the sequential runs
exactly, and ``dp_frontier`` can run a WHOLE sigma grid as lanes of one
vmapped scan per protocol stage (the transforms differ only in the cheap
eager exchange between stages).
"""
from __future__ import annotations

from math import ceil
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.apcvfl_paper import TABULAR as HP
from repro.core import comm, multiparty, pipeline
from repro.core.multiparty import VFLScenarioK
from repro.experiments.results import RunResult

# domain separator for exchange randomness: keyed off the run seed so the
# sequential and replicated paths derive identical noise for a given seed
EXCHANGE_SALT = 0xD0_5E

MECHANISMS = ("gaussian", "laplace")
QUANT_MODES = ("int8", "sign")


class ExchangeTransform:
    """Base: subclasses implement ``apply(z, key) -> (received, wire)``
    where ``received`` is the fp32 array the active party reconstructs
    and ``wire`` lists the actually-transmitted parts as ``(name_suffix,
    nbytes, dtype)``.  ``exchange`` (the ``comm.exchange_array`` hook)
    derives the deterministic key, accounts the wire parts, and returns
    the received array."""

    def apply(self, z, key) -> Tuple[jnp.ndarray, List[tuple]]:
        raise NotImplementedError

    def exchange(self, channel: comm.Channel, what: str, z, *,
                 seed: int = 0, link: int = 0,
                 direction: str = comm.UPLINK):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), EXCHANGE_SALT),
            link)
        received, wire = self.apply(jnp.asarray(z, jnp.float32), key)
        for suffix, nbytes, dtype in wire:
            channel.send(what + suffix, nbytes, direction=direction,
                         dtype=dtype)
        return received.astype(jnp.float32)


class ClippedNoise(ExchangeTransform):
    """Row-norm clipping + additive DP noise on the exchanged latents.

    ``clip`` bounds each row's L2 (gaussian) or L1 (laplace) norm — the
    per-row sensitivity — and the noise scale is ``sigma * clip``
    (``clip=None``: no clipping, sensitivity taken as 1.0).  The wire
    form stays fp32 (noise does not compress)."""

    def __init__(self, sigma: float = 0.0, mechanism: str = "gaussian",
                 clip: Optional[float] = None):
        if mechanism not in MECHANISMS:
            raise ValueError(f"mechanism must be one of {MECHANISMS}, "
                             f"got {mechanism!r}")
        if sigma < 0:
            raise ValueError(f"sigma must be >= 0, got {sigma}")
        if clip is not None and clip <= 0:
            raise ValueError(f"clip must be positive, got {clip}")
        self.sigma = float(sigma)
        self.mechanism = mechanism
        self.clip = None if clip is None else float(clip)

    def apply(self, z, key):
        if self.clip is not None:
            ord_ = 2 if self.mechanism == "gaussian" else 1
            norms = jnp.linalg.norm(z, ord=ord_, axis=1, keepdims=True)
            z = z * jnp.minimum(jnp.float32(1.0),
                                self.clip / jnp.maximum(norms, 1e-12))
        if self.sigma > 0.0:
            scale = self.sigma * (1.0 if self.clip is None else self.clip)
            draw = (jax.random.normal if self.mechanism == "gaussian"
                    else jax.random.laplace)
            z = z + scale * draw(key, z.shape, jnp.float32)
        return z, [("", int(z.size) * 4, "float32")]


class Quantize(ExchangeTransform):
    """Per-feature symmetric quantization of the exchanged latents.

    ``"int8"``: scale_j = absmax_j / 127, payload one int8 per element
    plus fp32 scales.  ``"sign"``: 1 bit per element (packed —
    ceil(n*m/8) wire bytes, dtype ``"sign1"``) times the per-feature mean
    magnitude.  The receiver consumes the dequantized fp32 array."""

    def __init__(self, mode: str = "int8"):
        if mode not in QUANT_MODES:
            raise ValueError(f"quantize mode must be one of {QUANT_MODES}, "
                             f"got {mode!r}")
        self.mode = mode

    def apply(self, z, key):
        del key                                  # deterministic transform
        n, m = z.shape
        if self.mode == "int8":
            scale = jnp.maximum(jnp.max(jnp.abs(z), axis=0),
                                1e-12) / 127.0
            q = jnp.clip(jnp.round(z / scale), -127.0, 127.0)
            deq = q.astype(jnp.float32) * scale
            wire = [("/q8", n * m, "int8"), ("/scale", m * 4, "float32")]
        else:
            scale = jnp.mean(jnp.abs(z), axis=0)
            deq = jnp.sign(z) * scale
            wire = [("/sign", ceil(n * m / 8), "sign1"),
                    ("/scale", m * 4, "float32")]
        return deq, wire


class Chain(ExchangeTransform):
    """Stages applied in order at the sender; the LAST stage's wire parts
    are what actually crosses the link (earlier stages are local)."""

    def __init__(self, stages: Sequence[ExchangeTransform]):
        if len(stages) < 2:
            raise ValueError("Chain needs >= 2 stages; use the stage "
                             "directly otherwise")
        self.stages = tuple(stages)

    def apply(self, z, key):
        wire = [("", int(z.size) * 4, "float32")]
        for j, stage in enumerate(self.stages):
            z, wire = stage.apply(z, jax.random.fold_in(key, j))
        return z, wire


def make_transform(*, sigma: float = 0.0, mechanism: str = "gaussian",
                   clip: Optional[float] = None,
                   quantize: Optional[str] = None
                   ) -> Optional[ExchangeTransform]:
    """Build the defense chain from plain knobs; ``None`` when every
    defense is off — the identity path, so a sigma-0 run stays
    bit-identical to the undefended protocol."""
    stages: List[ExchangeTransform] = []
    if sigma > 0.0 or clip is not None:
        stages.append(ClippedNoise(sigma, mechanism, clip))
    elif mechanism not in MECHANISMS:      # validate even when unused
        raise ValueError(f"mechanism must be one of {MECHANISMS}, "
                         f"got {mechanism!r}")
    if quantize is not None:
        stages.append(Quantize(quantize))
    if not stages:
        return None
    return stages[0] if len(stages) == 1 else Chain(stages)


# ---------------------------------------------------------------------------
# the defended protocol as a registered method
# ---------------------------------------------------------------------------

def _tag_dp(res: RunResult, *, sigma: float) -> RunResult:
    res.method = "apcvfl_dp"
    res.metrics = dict(res.metrics)
    res.metrics["dp_sigma"] = float(sigma)
    res.metrics["exchange_bytes"] = float(
        res.comm.get("by_stage", {}).get("step1", 0))
    return res


def run_apcvfl_dp(sc, *, sigma: float = 0.0, mechanism: str = "gaussian",
                  clip: Optional[float] = None,
                  quantize: Optional[str] = None, lam: float = HP.lam,
                  kind: str = HP.kind, seed: int = 0,
                  batch_size: int = HP.batch_size,
                  max_epochs: int = HP.max_epochs,
                  patience: int = HP.patience, lr: float = HP.lr,
                  use_kernel: bool = False,
                  ablation: bool = False) -> RunResult:
    """The full protocol with a hardened exchange (``@register_method
    ("apcvfl_dp")``) for one seed: ``run_apcvfl_dp_replicated`` with one
    seed lane.  With every defense off this IS ``run_apcvfl``."""
    return run_apcvfl_dp_replicated(
        [sc], seeds=[seed], sigma=sigma, mechanism=mechanism, clip=clip,
        quantize=quantize, lam=lam, kind=kind, batch_size=batch_size,
        max_epochs=max_epochs, patience=patience, lr=lr,
        use_kernel=use_kernel, ablation=ablation)[0]


def run_apcvfl_dp_replicated(scenarios, *, seeds, sigma: float = 0.0,
                             mechanism: str = "gaussian",
                             clip: Optional[float] = None,
                             quantize: Optional[str] = None,
                             lam: float = HP.lam, kind: str = HP.kind,
                             batch_size: int = HP.batch_size,
                             max_epochs: int = HP.max_epochs,
                             patience: int = HP.patience, lr: float = HP.lr,
                             use_kernel: bool = False,
                             ablation: bool = False,
                             mesh=None) -> List[RunResult]:
    """Seed replicas of one defended grid cell through the replica-lane
    engine: the transform is shared across seeds (per-seed noise keys),
    every protocol stage S lanes of one vmapped scan.  K-party scenarios
    go to ``run_apcvfl_k_replicated`` (every passive link gets the same
    transform, link-separated noise)."""
    t = make_transform(sigma=sigma, mechanism=mechanism, clip=clip,
                       quantize=quantize)
    kw = dict(seeds=seeds, lam=lam, kind=kind, batch_size=batch_size,
              max_epochs=max_epochs, patience=patience, lr=lr,
              use_kernel=use_kernel, ablation=ablation, exchange=t,
              mesh=mesh)
    if scenarios and isinstance(scenarios[0], VFLScenarioK):
        results = multiparty.run_apcvfl_k_replicated(scenarios, **kw)
    else:
        results = pipeline.run_apcvfl_replicated(scenarios, **kw)
    return [_tag_dp(r, sigma=sigma) for r in results]


def dp_frontier(sc, sigmas: Sequence[float], *,
                mechanism: str = "gaussian", clip: Optional[float] = None,
                quantize: Optional[str] = None, seed: int = 0,
                lam: float = HP.lam, kind: str = HP.kind,
                batch_size: int = HP.batch_size,
                max_epochs: int = HP.max_epochs,
                patience: int = HP.patience, lr: float = HP.lr,
                use_kernel: bool = False, mesh=None) -> List[RunResult]:
    """The utility side of the utility-vs-leakage frontier: run the WHOLE
    sigma grid as replica lanes of one protocol — one ``RunResult`` per
    sigma, each stage (2S g1 lanes, S g2 lanes, S g3 lanes) a single
    vmapped dispatch, the per-lane exchanges differing only in their
    (cheap, eager) transform.  All lanes share the run seed, so the
    sigma=0 lane reproduces the undefended ``run_apcvfl(sc, seed=seed)``
    within replica-lane tolerance."""
    transforms = [make_transform(sigma=float(s), mechanism=mechanism,
                                 clip=clip, quantize=quantize)
                  for s in sigmas]
    results = pipeline.run_apcvfl_replicated(
        sc, seeds=[seed] * len(transforms), lam=lam, kind=kind,
        batch_size=batch_size, max_epochs=max_epochs, patience=patience,
        lr=lr, use_kernel=use_kernel, exchange=transforms, mesh=mesh)
    return [_tag_dp(r, sigma=float(s)) for r, s in zip(results, sigmas)]
