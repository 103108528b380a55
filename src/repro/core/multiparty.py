"""K-participant APC-VFL (paper Sec. 3 formalizes K parties; the
experiments use K=2 — this module implements the general protocol).

One active participant (holds labels), K-1 passive participants. Step ①
runs at every party; each passive sends its aligned-row latents to the
active party (K-1 single exchanges — still ONE round per link, the paper's
claim is per-pair); steps ②-④ run at the active party on the concat of all
K latent blocks.

Alignment is the row-intersection across ALL parties, computed as K-1
genuine pairwise PSIs (active vs each passive) whose results are
intersected locally at the active party.  Each link is charged for the
active party's FULL hashed-ID upload — a real pairwise PSI cannot send the
already-shrunk running intersection, which would both leak information
about the other links and under-count bytes — so total PSI traffic is
monotone in K.

All K g1 stages (active + passives) train together through the lane
engine (``training.train_lanes``, one lane per party): the parties' params
and datasets are stacked by shape group on a leading lane axis, and the
whole fit runs as one vmapped scan with one host sync.  Parties that
early-stop keep their params behind a per-lane ``live`` flag, so the batch
shape stays static; see the ``core.training`` module docstring.  Stage
handoffs stay device-resident (latents feed g2/g3 as jax arrays; channel
accounting reads only shapes), matching ``core.pipeline``.

One implementation runs the protocol: ``run_apcvfl_k_replicated`` takes S
seed replicates through every stage as lanes, and ``run_apcvfl_k`` is its
one-seed case.  Hyperparameter defaults come from
``configs.apcvfl_paper.TABULAR``; both return the unified
``experiments.results.RunResult``, whose ``channels`` tuple holds one
measured ``comm.Channel`` per passive link (``rounds`` is the paper's
per-link claim: ONE data exchange)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.apcvfl_paper import TABULAR as HP
from repro.core import autoencoder as ae
from repro.core import classifier as clf
from repro.core import comm
from repro.core import distill
from repro.core import training
from repro.core.psi import id_positions, psi
from repro.data.synthetic import TabularDataset
from repro.data.vertical import ParticipantData
from repro.experiments.results import RunResult


@dataclass
class VFLScenarioK:
    name: str
    active: ParticipantData
    passives: List[ParticipantData]
    n_aligned: int
    n_classes: int


def make_scenario_k(ds: TabularDataset, *, n_parties: int,
                    n_active_features: int, n_aligned: int,
                    seed: int = 0) -> VFLScenarioK:
    """Split columns among K parties (active gets ``n_active_features``,
    passives share the rest round-robin); rows: ``n_aligned`` common to all,
    remainder split disjointly."""
    assert n_parties >= 2
    rng = np.random.RandomState(seed + 2000)
    d = ds.x.shape[1]
    cols = rng.permutation(d)
    a_cols = np.sort(cols[:n_active_features])
    rest = cols[n_active_features:]
    p_cols = [np.sort(rest[i::n_parties - 1]) for i in range(n_parties - 1)]
    assert all(len(c) for c in p_cols), "not enough features for K parties"

    n = len(ds.x)
    perm = rng.permutation(n)
    aligned = perm[:n_aligned]
    rest_rows = np.array_split(perm[n_aligned:], n_parties)
    rows = [np.concatenate([aligned, rr]) for rr in rest_rows]

    active = ParticipantData(x=ds.x[rows[0]][:, a_cols], ids=ds.ids[rows[0]],
                             y=ds.y[rows[0]])
    passives = [ParticipantData(x=ds.x[rows[i + 1]][:, p_cols[i]],
                                ids=ds.ids[rows[i + 1]])
                for i in range(n_parties - 1)]
    return VFLScenarioK(ds.name, active, passives, n_aligned, ds.n_classes)


def align_k(active_ids: np.ndarray, passive_ids: List[np.ndarray]):
    """Multi-party alignment as K-1 genuine pairwise PSIs (active vs each
    passive), intersected locally at the active party.  Each link is
    charged for the active party's FULL hashed-ID upload — sending the
    already-shrunk running intersection instead would both leak the other
    links' results and under-count bytes.  Returns (common_ids sorted,
    per-link channels)."""
    if not passive_ids:          # degenerate: nothing to align against
        common = np.unique(np.asarray(active_ids))   # sorted, per contract
        if len(common) != len(active_ids):           # same policy as psi()
            raise ValueError("PSI requires unique IDs: got "
                             f"{len(active_ids)} ids, {len(common)} distinct")
        return common, []
    channels = [comm.Channel() for _ in passive_ids]
    pair_commons = []
    for ids, ch in zip(passive_ids, channels):
        c, _, _ = psi(active_ids, ids, channel=ch)
        pair_commons.append(c)
    common = pair_commons[0]
    for c in pair_commons[1:]:
        common = np.intersect1d(common, c)
    return common, channels


def run_apcvfl_k(sc: VFLScenarioK, *, lam: float = HP.lam,
                 kind: str = HP.kind, seed: int = 0,
                 batch_size: int = HP.batch_size,
                 max_epochs: int = HP.max_epochs,
                 patience: int = HP.patience, lr: float = HP.lr,
                 use_kernel: bool = False,
                 ablation: bool = False, exchange=None) -> RunResult:
    """K-party protocol for one seed: ``run_apcvfl_k_replicated`` with one
    seed lane (the same keyword surface as the 2-party ``run_apcvfl``)."""
    return run_apcvfl_k_replicated(
        sc, seeds=[seed], lam=lam, kind=kind, batch_size=batch_size,
        max_epochs=max_epochs, patience=patience, lr=lr,
        use_kernel=use_kernel, ablation=ablation, exchange=exchange)[0]


def _index_of(ids: np.ndarray, subset: np.ndarray) -> np.ndarray:
    pos = id_positions(ids)
    return np.asarray([pos[int(s)] for s in subset], dtype=np.int64)


# ---------------------------------------------------------------------------
# replica-lane execution: all seeds of one K-party grid cell per dispatch
# ---------------------------------------------------------------------------

def run_apcvfl_k_replicated(scenarios, *, seeds, lam: float = HP.lam,
                            kind: str = HP.kind,
                            batch_size: int = HP.batch_size,
                            max_epochs: int = HP.max_epochs,
                            patience: int = HP.patience, lr: float = HP.lr,
                            use_kernel: bool = False,
                            ablation: bool = False, exchange=None,
                            mesh=None) -> List[RunResult]:
    """K-party protocol for S seed replicates of one grid cell, every
    stage one ``training.train_lanes`` dispatch: ALL parties of ALL seeds
    train their g1 stage as S*K lanes of one vmapped scan, then S g2
    lanes, then S g3 lanes — the K-party twin of
    ``pipeline.run_apcvfl_replicated`` (same contract: one scenario
    shared by every seed or one equal-shape scenario per seed; one
    ``RunResult`` per seed; ``ablation`` and ``exchange`` as there, each
    passive link deriving its own transform randomness from its link
    index).  ``mesh`` shards every stage's lane axis across devices (see
    ``training.train_lanes``)."""
    seeds = [int(s) for s in seeds]
    S = len(seeds)
    scs = ([scenarios] * S if isinstance(scenarios, VFLScenarioK)
           else list(scenarios))
    if len(scs) != S:
        raise ValueError(f"run_apcvfl_k_replicated: {len(scs)} scenarios "
                         f"for {S} seeds")
    if S == 0:
        return []
    exchanges = comm.normalize_exchange(exchange, S)
    train_kw = dict(batch_size=batch_size, max_epochs=max_epochs,
                    patience=patience, lr=lr, mesh=mesh)
    K = len(scs[0].passives) + 1

    aligns = [align_k(sc.active.ids, [p.ids for p in sc.passives])
              for sc in scs]
    idx_as = [_index_of(sc.active.ids, common)
              for sc, (common, _) in zip(scs, aligns)]
    idx_pss = [[_index_of(p.ids, common) for p in sc.passives]
               for sc, (common, _) in zip(scs, aligns)]
    keys = [jax.random.split(jax.random.PRNGKey(s), K + 2) for s in seeds]
    epochs = [{} for _ in range(S)]

    if not ablation:
        # --- step 1: S * K g1 lanes (every party of every seed) ------------
        lanes = []
        for sc, s, ks in zip(scs, seeds, keys):
            lanes.append(training.LaneSpec(
                ae.init_autoencoder(ks[0], ae.table3_encoder(
                    "g1_active", sc.active.x.shape[1])),
                {"x": sc.active.x}, s))
            for i, p in enumerate(sc.passives):
                lanes.append(training.LaneSpec(
                    ae.init_autoencoder(ks[i + 1], ae.table3_encoder(
                        "g1_passive", p.x.shape[1])),
                    {"x": p.x}, s + i + 1))
        g1 = training.train_lanes(lanes, ae.masked_recon_loss, **train_kw)

        # --- step 2: S g2 lanes on device-resident joint latents -----------
        zjs, zps = [], []
        for i, (sc, (_, channels)) in enumerate(zip(scs, aligns)):
            ra = g1[K * i]
            epochs[i]["g1_active"] = ra.epochs_run
            za = ae.encode(ra.params,
                           jnp.asarray(sc.active.x[idx_as[i]]))
            blocks = [za]
            for j, (p, idx_p, ch) in enumerate(zip(sc.passives,
                                                   idx_pss[i], channels)):
                rp = g1[K * i + j + 1]
                epochs[i][f"g1_passive{j}"] = rp.epochs_run
                zp = ae.encode(rp.params, jnp.asarray(p.x[idx_p]))
                zp = comm.exchange_array(
                    ch, f"step1/Z_passive{j}_aligned", zp,
                    transform=exchanges[i], seed=seeds[i], link=j)
                blocks.append(zp)
            zps.append(jnp.concatenate(blocks[1:], axis=1))
            zjs.append(jnp.concatenate(blocks, axis=1).astype(jnp.float32))
        g2 = training.train_lanes(
            [training.LaneSpec(
                ae.init_autoencoder(ks[-2],
                                    ae.table3_encoder("g2", zj.shape[1])),
                {"x": zj}, s + 100)
             for zj, s, ks in zip(zjs, seeds, keys)],
            ae.masked_recon_loss, **train_kw)
        zts = [ae.encode(r2.params, zj) for r2, zj in zip(g2, zjs)]
        m2 = zts[0].shape[1]
        for i, r2 in enumerate(g2):
            epochs[i]["g2"] = r2.epochs_run
    else:
        m2 = ae.table3_encoder("g2", 1)[-1]
        zts = [None] * S
        zps = [None] * S

    # --- step 3: S g3 distillation lanes ------------------------------------
    g3_lanes = []
    for sc, s, ks, zt, idx_a in zip(scs, seeds, keys, zts, idx_as):
        xa = sc.active.x
        z_teacher = jnp.zeros((len(xa), m2), jnp.float32)
        mask = jnp.zeros((len(xa),), jnp.float32)
        if not ablation:
            z_teacher = z_teacher.at[idx_a].set(zt)
            mask = mask.at[idx_a].set(1.0)
        g3_lanes.append(training.LaneSpec(
            ae.init_autoencoder(ks[-1], ae.table3_encoder("g3",
                                                          xa.shape[1])),
            {"x": xa, "z_teacher": z_teacher, "aligned": mask}, s + 200))
    g3 = training.train_lanes(
        g3_lanes, distill.make_lanes_loss(lam, kind, use_kernel=use_kernel),
        **train_kw)

    # --- step 4: classifier per seed (see pipeline.run_apcvfl_replicated) --
    results = []
    data_rounds = 0 if ablation else comm.APCVFL_ROUNDS
    for i, (sc, s, r3, (common, channels)) in enumerate(zip(scs, seeds, g3,
                                                            aligns)):
        epochs[i]["g3"] = r3.epochs_run
        z_all = ae.encode(r3.params, jnp.asarray(sc.active.x))
        metrics = clf.kfold_cv(z_all, sc.active.y, sc.n_classes, seed=s)
        params = {"g3": r3.params}
        artifacts = None
        if not ablation:
            params["g1_active"] = g1[K * i].params
            params["g2"] = g2[i].params
            artifacts = {"aligned_ids": np.asarray(common),
                         "z_passive_aligned": zps[i]}
        results.append(RunResult(
            method="apcvfl", metrics=metrics, rounds=data_rounds,
            epochs=epochs[i], comm=comm.summarize(channels), seed=s,
            z_dim=m2, params=params, channels=tuple(channels),
            artifacts=artifacts))
    return results
