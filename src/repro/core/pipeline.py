"""APC-VFL: the four-step protocol (paper Fig. 3) plus the aligned-only
adaptation used against SplitNN (paper Fig. 4) and the Appendix-F
encoder-quality probe (Algorithm 1).

Step 1  local representation learning   (every participant, autoencoder)
        -> passive sends Z_p[aligned] to active: THE single exchange.
Step 2  aligned representation learning (active, autoencoder g2 on
        concat(Z_a, Z_p) of aligned rows)
Step 3  knowledge distillation          (active, student AE g3 on the FULL
        active dataset, Eq. 5 masked loss)
Step 4  classifier on Z = g3(X_active), labels from the active party.

One implementation runs the protocol: ``run_apcvfl_replicated`` takes S
seed replicates of one grid cell through every stage together, each stage
S (or 2S, for the two g1s) lanes of one ``training.train_lanes`` call —
one compile and one host sync per stage, not S of each.  ``run_apcvfl``
is its one-seed case, and the aligned-only pair is built the same way.
The ``*_replicated`` entry points take an optional ``mesh``
(``repro.launch.mesh.make_lane_mesh``) that shards every stage's lane
axis across devices — same computation, device-parallel lanes.

Stage handoffs are device-resident: encoder outputs feed the next stage as
jax arrays (the lane engine gathers its train/val splits on device) and
the channel accounting reads only shapes/dtypes, so the handoffs
themselves add NO host round-trips — what remains is the engine's single
early-stop sync per stage and the k-fold probe's (``clf.kfold_cv_many``,
one sync for every seed's folds).

Hyperparameter defaults come from ``configs.apcvfl_paper.TABULAR`` (the
paper's Appendix-B settings); every entry point returns the unified
``experiments.results.RunResult``, so declarative specs
(``repro.experiments``) and direct calls see identical behavior.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.apcvfl_paper import TABULAR as HP
from repro.core import autoencoder as ae
from repro.core import classifier as clf
from repro.core import comm
from repro.core import distill
from repro.core import training
from repro.core.psi import psi
from repro.core.spans import span
from repro.data.vertical import VFLScenario
from repro.experiments.results import RunResult


def run_apcvfl(sc: VFLScenario, *, lam: float = HP.lam, kind: str = HP.kind,
               seed: int = 0, batch_size: int = HP.batch_size,
               max_epochs: int = HP.max_epochs, patience: int = HP.patience,
               lr: float = HP.lr, use_kernel: bool = False,
               ablation: bool = False, exchange=None) -> RunResult:
    """Full protocol for one seed: ``run_apcvfl_replicated`` with one seed
    lane (its docstring gives ``ablation`` and ``exchange``)."""
    return run_apcvfl_replicated(
        sc, seeds=[seed], lam=lam, kind=kind, batch_size=batch_size,
        max_epochs=max_epochs, patience=patience, lr=lr,
        use_kernel=use_kernel, ablation=ablation, exchange=exchange)[0]


# ---------------------------------------------------------------------------
# replica-lane execution: all seeds of one grid cell per stage dispatch
# ---------------------------------------------------------------------------

def _normalize_replicas(fn_name: str, scenarios, seeds):
    """Shared contract of the ``*_replicated`` entry points: int seeds,
    one scenario broadcast to every seed or exactly one per seed."""
    seeds = [int(s) for s in seeds]
    S = len(seeds)
    scs = ([scenarios] * S if isinstance(scenarios, VFLScenario)
           else list(scenarios))
    if len(scs) != S:
        raise ValueError(f"{fn_name}: {len(scs)} scenarios for {S} seeds")
    return scs, seeds


def run_apcvfl_replicated(scenarios, *, seeds, lam: float = HP.lam,
                          kind: str = HP.kind,
                          batch_size: int = HP.batch_size,
                          max_epochs: int = HP.max_epochs,
                          patience: int = HP.patience, lr: float = HP.lr,
                          use_kernel: bool = False,
                          ablation: bool = False, exchange=None,
                          mesh=None) -> list:
    """Full protocol for S seed replicates of one grid cell, every stage
    one ``training.train_lanes`` dispatch: the two g1s of all seeds run as
    2S lanes, g2 as S lanes, g3 as S lanes — one compile and one host sync
    per stage for the whole replica set instead of S of each.

    ``scenarios`` is a single ``VFLScenario`` shared by every seed, or a
    sequence of per-seed scenarios of EQUAL shapes (a sweep group: same
    dataset / n_aligned / feature split, different partition seeds).
    Returns one ``RunResult`` per seed; a lane's trajectories are its own,
    so each matches a one-seed run to float tolerance
    (tests/test_replicas.py pins the parity).

    ``ablation=True`` trains g3 WITHOUT the distillation term (the
    paper's 'Ablation' curves — isolates the nonlinear-encoder gain).
    ``exchange`` hardens the single latent exchange: an
    ``ExchangeTransform`` (``repro.robustness.defense`` — DP noise,
    quantization) applied at the sender.  Everything downstream of the
    exchange (g2, g3, the serving artifacts) consumes the RECEIVED
    latents, and the channel accounts the transformed wire bytes; ``None``
    is the paper's plain fp32 exchange.  ``use_kernel=True`` runs the g3
    lanes through the fused Eq. 5 Pallas kernel
    (``distill.make_lanes_loss(use_kernel=True)``).  ``mesh`` shards every
    stage's lane axis across devices (see ``training.train_lanes``).

    ``exchange`` is one ``ExchangeTransform`` shared by every replica or
    a per-replica sequence (entries may be ``None``): a whole defense
    grid — e.g. one sigma per lane via ``robustness.defense.dp_frontier``
    — runs its g1/g2/g3 stages as lanes of the same vmapped scans, with
    only the cheap eager exchange differing per lane.  Per-lane noise
    keys derive from each lane's SEED (not its lane index), so a lane's
    noise is ``run_apcvfl(sc, seed=s, exchange=t)``'s."""
    scs, seeds = _normalize_replicas("run_apcvfl_replicated", scenarios,
                                     seeds)
    S = len(seeds)
    if S == 0:
        return []
    exchanges = comm.normalize_exchange(exchange, S)
    train_kw = dict(batch_size=batch_size, max_epochs=max_epochs,
                    patience=patience, lr=lr, mesh=mesh)

    channels = [comm.Channel() for _ in range(S)]
    psis = [psi(sc.active.ids, sc.passive.ids, channel=ch)
            for sc, ch in zip(scs, channels)]
    keys = [jax.random.split(jax.random.PRNGKey(s), 4) for s in seeds]
    epochs = [{} for _ in range(S)]

    if not ablation:
        # --- Step 1: 2S g1 lanes (active + passive per seed) ---------------
        with span("g1"):
            lanes = []
            for sc, s, (k1, k2, _, _) in zip(scs, seeds, keys):
                lanes.append(training.LaneSpec(
                    ae.init_autoencoder(k1, ae.table3_encoder(
                        "g1_active", sc.active.x.shape[1])),
                    {"x": sc.active.x}, s))
                lanes.append(training.LaneSpec(
                    ae.init_autoencoder(k2, ae.table3_encoder(
                        "g1_passive", sc.passive.x.shape[1])),
                    {"x": sc.passive.x}, s + 1))
            g1 = training.train_lanes(
                lanes, ae.make_masked_recon_loss(use_kernel), **train_kw)

        # --- the exchange: aligned passive latents, device-resident --------
        with span("exchange"):
            zjs, zps = [], []
            for i, (sc, ch, (_, idx_a, idx_p)) in enumerate(
                    zip(scs, channels, psis)):
                ra, rp = g1[2 * i], g1[2 * i + 1]
                epochs[i]["g1_active"] = ra.epochs_run
                epochs[i]["g1_passive"] = rp.epochs_run
                za_al = ae.encode(ra.params, jnp.asarray(sc.active.x[idx_a]))
                zp_al = ae.encode(rp.params,
                                  jnp.asarray(sc.passive.x[idx_p]))
                zp_al = comm.exchange_array(ch, "step1/Z_passive_aligned",
                                            zp_al, transform=exchanges[i],
                                            seed=seeds[i])
                zps.append(zp_al)
                zjs.append(jnp.concatenate([za_al, zp_al],
                                           axis=1).astype(jnp.float32))

        # --- Step 2: S g2 lanes on device-resident joint latents -----------
        with span("g2"):
            g2 = training.train_lanes(
                [training.LaneSpec(
                    ae.init_autoencoder(k3, ae.table3_encoder(
                        "g2", zj.shape[1])),
                    {"x": zj}, s + 2)
                 for zj, s, (_, _, k3, _) in zip(zjs, seeds, keys)],
                ae.make_masked_recon_loss(use_kernel), **train_kw)
            zts = [ae.encode(r2.params, zj) for r2, zj in zip(g2, zjs)]
        m2 = zts[0].shape[1]
        for i, r2 in enumerate(g2):
            epochs[i]["g2"] = r2.epochs_run
    else:
        m2 = ae.table3_encoder("g2", 1)[-1]
        zts = [None] * S
        zps = [None] * S

    # --- Step 3: S g3 distillation lanes ------------------------------------
    with span("g3"):
        g3_lanes = []
        for sc, s, (_, _, _, k4), zt, (_, idx_a, _) in zip(
                scs, seeds, keys, zts, psis):
            xa = sc.active.x
            z_teacher = jnp.zeros((len(xa), m2), jnp.float32)
            mask = jnp.zeros((len(xa),), jnp.float32)
            if not ablation:
                z_teacher = z_teacher.at[idx_a].set(zt)
                mask = mask.at[idx_a].set(1.0)
            w3 = ae.table3_encoder("g3", xa.shape[1])
            assert w3[-1] == m2, ("M3 == M2: dimensional consistency "
                                  "(Sec. 4.3)")
            g3_lanes.append(training.LaneSpec(
                ae.init_autoencoder(k4, w3),
                {"x": xa, "z_teacher": z_teacher, "aligned": mask}, s + 3))
        g3 = training.train_lanes(
            g3_lanes, distill.make_lanes_loss(lam, kind,
                                              use_kernel=use_kernel),
            **train_kw)

    # --- Step 4: classifier probes, all S seeds' folds as one doubly-
    # vmapped lane dispatch (S x k probe fits, one compile + one sync).
    # Per-seed metrics match kfold_cv(z, ..., seed=s) within lane-engine
    # tolerance (tests/test_replicas.py pins the band).
    with span("probe"):
        z_alls = [ae.encode(r3.params, jnp.asarray(sc.active.x))
                  for sc, r3 in zip(scs, g3)]
        metrics_list = clf.kfold_cv_many(
            z_alls, [sc.active.y for sc in scs], scs[0].n_classes,
            seeds=seeds)
    results = []
    data_rounds = 0 if ablation else comm.APCVFL_ROUNDS
    for i, (s, ch, r3, ep, metrics) in enumerate(zip(seeds, channels, g3,
                                                     epochs, metrics_list)):
        ep["g3"] = r3.epochs_run
        params = {"g3": r3.params}
        artifacts = None
        if not ablation:
            params["g1_active"] = g1[2 * i].params
            params["g2"] = g2[i].params
            artifacts = {"aligned_ids": np.asarray(psis[i][0]),
                         "z_passive_aligned": zps[i]}
        results.append(RunResult(
            method="apcvfl", metrics=metrics, rounds=data_rounds,
            epochs=ep, comm=ch.summary(), seed=s, z_dim=m2,
            params=params, channels=(ch,), artifacts=artifacts))
    return results


def run_local_baseline(sc, seed: int = 0) -> dict:
    """Paper 'Local': probe on raw active features.  Works for 2-party and
    K-party scenarios (only ``sc.active`` is touched); returns the bare
    metrics dict — the ``experiments`` registry wraps it into a
    ``RunResult``."""
    return clf.kfold_cv(sc.active.x, sc.active.y, sc.n_classes, seed=seed)


# ---------------------------------------------------------------------------
# aligned-only adaptation (paper Fig. 4, for the SplitNN comparison)
# ---------------------------------------------------------------------------

def run_apcvfl_aligned_only(sc: VFLScenario, *, seed: int = 0,
                            batch_size: int = HP.batch_size,
                            max_epochs: int = HP.max_epochs,
                            patience: int = HP.patience, lr: float = HP.lr,
                            test_size: int = HP.test_size) -> RunResult:
    """The aligned-only adaptation for one seed:
    ``run_apcvfl_aligned_only_replicated`` with one seed lane."""
    return run_apcvfl_aligned_only_replicated(
        sc, seeds=[seed], batch_size=batch_size, max_epochs=max_epochs,
        patience=patience, lr=lr, test_size=test_size)[0]


def run_apcvfl_aligned_only_replicated(scenarios, *, seeds,
                                       batch_size: int = HP.batch_size,
                                       max_epochs: int = HP.max_epochs,
                                       patience: int = HP.patience,
                                       lr: float = HP.lr,
                                       test_size: int = HP.test_size,
                                       mesh=None) -> list:
    """Classical fully-aligned setting: train the classifier directly on
    the joint latents g2(concat(Z_a, Z_p)); distillation is skipped (no
    unaligned rows exist to distill into).

    S seed replicates, every stage one ``train_lanes`` dispatch (2S g1
    lanes, S g2 lanes).  Same contract as ``run_apcvfl_replicated``: one
    scenario shared or one per seed, one ``RunResult`` per seed.  ``mesh``
    shards every stage's lane axis across devices."""
    scs, seeds = _normalize_replicas("run_apcvfl_aligned_only_replicated",
                                     scenarios, seeds)
    S = len(seeds)
    if S == 0:
        return []
    train_kw = dict(batch_size=batch_size, max_epochs=max_epochs,
                    patience=patience, lr=lr, mesh=mesh)

    channels = [comm.Channel() for _ in range(S)]
    keys = [jax.random.split(jax.random.PRNGKey(s), 3) for s in seeds]
    cells = []                        # (xa, xp, y) aligned rows per seed
    for sc, ch in zip(scs, channels):
        _, idx_a, idx_p = psi(sc.active.ids, sc.passive.ids, channel=ch)
        cells.append((sc.active.x[idx_a], sc.passive.x[idx_p],
                      sc.active.y[idx_a]))

    lanes = []
    for (xa, xp, _), s, (k1, k2, _) in zip(cells, seeds, keys):
        lanes.append(training.LaneSpec(
            ae.init_autoencoder(k1, ae.table3_encoder("g1_active",
                                                      xa.shape[1])),
            {"x": xa}, s))
        lanes.append(training.LaneSpec(
            ae.init_autoencoder(k2, ae.table3_encoder("g1_passive",
                                                      xp.shape[1])),
            {"x": xp}, s + 1))
    g1 = training.train_lanes(lanes, ae.masked_recon_loss, **train_kw)

    zjs = []
    for i, ((xa, xp, _), ch) in enumerate(zip(cells, channels)):
        ra, rp = g1[2 * i], g1[2 * i + 1]
        za = ae.encode(ra.params, jnp.asarray(xa))
        zp = ae.encode(rp.params, jnp.asarray(xp))
        ch.send_array("step1/Z_passive_aligned", zp, direction="uplink")
        zjs.append(jnp.concatenate([za, zp], 1).astype(jnp.float32))
    g2 = training.train_lanes(
        [training.LaneSpec(
            ae.init_autoencoder(k3, ae.table3_encoder("g2", zj.shape[1])),
            {"x": zj}, s + 2)
         for zj, s, (_, _, k3) in zip(zjs, seeds, keys)],
        ae.masked_recon_loss, **train_kw)

    results = []
    for i, ((_, _, y), s, ch, zj, r2) in enumerate(zip(cells, seeds,
                                                       channels, zjs, g2)):
        z = np.asarray(ae.encode(r2.params, zj))
        rng = np.random.RandomState(s)
        perm = rng.permutation(len(z))
        te, tr = perm[:test_size], perm[test_size:]
        params = clf.fit_logreg(jnp.asarray(z[tr]), jnp.asarray(y[tr]),
                                scs[i].n_classes)
        pred = clf.predict(params, z[te])
        metrics = clf.f1_scores(y[te], pred, scs[i].n_classes)
        ra, rp = g1[2 * i], g1[2 * i + 1]
        results.append(RunResult(
            method="apcvfl_aligned_only", metrics=metrics, rounds=1,
            epochs={"g1_active": ra.epochs_run,
                    "g1_passive": rp.epochs_run, "g2": r2.epochs_run},
            comm=ch.summary(), seed=s, z_dim=z.shape[1],
            params={"g2": r2.params}, channels=(ch,)))
    return results


# ---------------------------------------------------------------------------
# Appendix F, Algorithm 1: encoder training with representation-quality probe
# ---------------------------------------------------------------------------

def train_encoder_with_probe(x: np.ndarray, y: np.ndarray, n_classes: int,
                             widths: list, *, metric: str = "accuracy",
                             k: int = 5, max_epochs: int = 30,
                             seed: int = 0) -> dict:
    """Runs Algorithm 1: per-epoch, k-fold CV the probe on Z=g(X).  Returns
    the loss curve, per-epoch metric sets M~, the raw-X metric set M, and
    the equivalence gap (Eq. 12)."""
    key = jax.random.PRNGKey(seed)
    params = ae.init_autoencoder(key, widths)
    history = {"loss": [], "probe": []}

    def cb(epoch, p, tl, vl):
        # per-epoch probe; ``p`` is device-resident and donated into the
        # next epoch, so everything derived from it is computed here
        z = np.asarray(ae.encode(p, jnp.asarray(x)))
        m = clf.kfold_cv(z, y, n_classes, k=k, seed=seed)
        history["probe"].append(m[metric])
        history["loss"].append(tl)

    training.train(params, {"x": x}, ae.recon_loss, max_epochs=max_epochs,
                   patience=max_epochs, seed=seed, epoch_callback=cb)
    base = clf.kfold_cv(x, y, n_classes, k=k, seed=seed)[metric]
    gap = base - (history["probe"][-1] if history["probe"] else 0.0)
    return {"history": history, "metric_raw_x": base, "gap": gap}
