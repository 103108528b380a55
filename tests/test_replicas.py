"""Replica-lane execution tests: vmapped-seed parity against the
sequential per-seed path (pipeline + sweep layers), the vmapped k-fold
classifier, the bincount f1 rewrite, the lanes distill loss, and the
``supports_replicas`` registry surface.

Tolerance discipline (same as ``test_train_many``): engine-level outputs
(params, epoch counts, comm bytes) match exactly or to float tolerance;
downstream metrics get a CV-noise band (0.03) because the linear probe
amplifies float-level z differences near its decision boundary — the PR-2
precedent for vmapped-vs-sequential protocol comparisons.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autoencoder as ae
from repro.core import classifier as clf
from repro.core import distill
from repro.core import pipeline
from repro.experiments import (ExperimentSpec, MethodSpec, get_method,
                               register_replicas, sweep)
from repro.experiments.registry import MethodEntry
from repro.experiments.specs import ScenarioSpec
from repro.experiments.sweeps import build_scenario

METRIC_TOL = 0.03     # CV-noise band for probe metrics (module docstring)


def _max_leaf_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# metrics: bincount f1 + vmapped k-fold
# ---------------------------------------------------------------------------

def _f1_scores_loop(y_true, y_pred, n_classes):
    """The pre-vectorization implementation (4 passes per class), kept
    here as the parity reference for the bincount rewrite."""
    tp = np.zeros(n_classes)
    fp = np.zeros(n_classes)
    fn = np.zeros(n_classes)
    support = np.zeros(n_classes)
    for c in range(n_classes):
        tp[c] = np.sum((y_pred == c) & (y_true == c))
        fp[c] = np.sum((y_pred == c) & (y_true != c))
        fn[c] = np.sum((y_pred != c) & (y_true == c))
        support[c] = np.sum(y_true == c)
    denom = 2 * tp + fp + fn
    f1c = np.where(denom > 0, 2 * tp / np.maximum(denom, 1), 0.0)
    micro_d = 2 * tp.sum() + fp.sum() + fn.sum()
    return {
        "accuracy": float(np.mean(y_true == y_pred)),
        "f1_micro": float(2 * tp.sum() / micro_d) if micro_d else 0.0,
        "f1_macro": float(np.mean(f1c)),
        "f1_weighted": float(np.sum(f1c * support) / max(support.sum(), 1)),
        "f1_binary": float(f1c[1]) if n_classes == 2 else float(np.mean(f1c)),
    }


@pytest.mark.parametrize("n_classes", [2, 4])
def test_f1_scores_bincount_matches_loop(n_classes):
    rng = np.random.RandomState(0)
    y_true = rng.randint(0, n_classes, 400)
    y_pred = rng.randint(0, n_classes, 400)
    got = clf.f1_scores(y_true, y_pred, n_classes)
    want = _f1_scores_loop(y_true, y_pred, n_classes)
    assert got.keys() == want.keys()
    for k in want:
        assert abs(got[k] - want[k]) < 1e-12, k


def test_f1_scores_empty_class():
    """A class absent from both y_true and y_pred gets f1=0 (not NaN) in
    both implementations."""
    y = np.array([0, 0, 1, 1])
    p = np.array([0, 1, 1, 0])
    got = clf.f1_scores(y, p, 3)
    want = _f1_scores_loop(y, p, 3)
    for k in want:
        assert abs(got[k] - want[k]) < 1e-12 and np.isfinite(got[k])


def test_kfold_cv_matches_per_fold_reference():
    """The single-jit vmapped k-fold (zero-weight-padded folds) must match
    k sequential fit_logreg fits on the identical fold assignment."""
    rng = np.random.RandomState(1)
    x = rng.randn(317, 6).astype(np.float32)   # 317 % 10 != 0: uneven folds
    y = (x[:, 0] + 0.5 * x[:, 1] + 0.2 * rng.randn(317) > 0).astype(np.int64)
    got = clf.kfold_cv(x, y, 2, k=10, seed=3)

    perm = np.random.RandomState(3).permutation(len(x))
    folds = np.array_split(perm, 10)
    accs = []
    for i in range(10):
        te = folds[i]
        tr = np.concatenate([folds[j] for j in range(10) if j != i])
        params = clf.fit_logreg(jnp.asarray(x[tr]), jnp.asarray(y[tr]), 2)
        pred = clf.predict(params, x[te])
        accs.append(clf.f1_scores(y[te], pred, 2))
    want = {k: float(np.mean([a[k] for a in accs])) for k in accs[0]}
    for k in want:
        assert abs(got[k] - want[k]) < 0.01, (k, got[k], want[k])


def test_kfold_cv_many_matches_per_seed():
    rng = np.random.RandomState(2)
    xs = [rng.randn(143, 5).astype(np.float32) for _ in range(3)]
    ys = [(x[:, 0] > 0).astype(np.int64) for x in xs]
    many = clf.kfold_cv_many(xs, ys, 2, k=5, seeds=[4, 5, 6])
    for x, y, s, got in zip(xs, ys, [4, 5, 6], many):
        want = clf.kfold_cv(x, y, 2, k=5, seed=s)
        for k in want:
            assert abs(got[k] - want[k]) < 0.01, (s, k)


# ---------------------------------------------------------------------------
# lanes distill loss
# ---------------------------------------------------------------------------

def test_make_lanes_loss_equals_make_loss_without_padding():
    key = jax.random.PRNGKey(0)
    params = ae.init_autoencoder(key, [8, 16, 4])
    x = jax.random.normal(key, (32, 8))
    batch = {"x": x, "z_teacher": jax.random.normal(key, (32, 4)),
             "aligned": (jax.random.uniform(key, (32,)) > 0.4).astype(
                 jnp.float32)}
    a = float(distill.make_loss(lam=0.5, kind="mae")(params, batch))
    b = float(distill.make_lanes_loss(lam=0.5, kind="mae")(
        params, {**batch, "mask": jnp.ones((8,)),
                 "row_w": jnp.ones((32,))}))
    assert abs(a - b) < 1e-6


@pytest.mark.parametrize("kind", ["mse", "mae"])
def test_make_lanes_loss_kernel_matches_reference(kind):
    """The fused-kernel lanes loss (Pallas fwd + closed-form VJP) must
    equal the reference lanes formula in value AND gradient, including
    under feature padding and zero-weight rows (the masked-column rescale
    trick is exact for 0/1 masks)."""
    key = jax.random.PRNGKey(7)
    params = ae.init_autoencoder(key, [10, 16, 4])
    x = jax.random.normal(key, (24, 10))
    fm = jnp.asarray([1.0] * 7 + [0.0] * 3)          # 3 padded columns
    rw = jnp.asarray([1.0] * 20 + [0.0] * 4)         # 4 padded rows
    batch = {"x": x * fm, "z_teacher": jax.random.normal(key, (24, 4)),
             "aligned": (jax.random.uniform(key, (24,)) > 0.4).astype(
                 jnp.float32),
             "mask": fm, "row_w": rw}
    ref_fn = distill.make_lanes_loss(lam=0.3, kind=kind)
    ker_fn = distill.make_lanes_loss(lam=0.3, kind=kind, use_kernel=True)
    vr, gr = jax.value_and_grad(ref_fn)(params, batch)
    vk, gk = jax.value_and_grad(ker_fn)(params, batch)
    assert abs(float(vr) - float(vk)) < 1e-6
    assert _max_leaf_diff(gr, gk) < 1e-5
    assert ref_fn.cache_key != ker_fn.cache_key    # distinct engines


# ---------------------------------------------------------------------------
# replicated pipeline parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def replica_cells():
    seeds = [0, 1]
    scs = [build_scenario(ScenarioSpec(dataset="bcw", n_aligned=120,
                                       n_active_features=5, seed=s))
           for s in seeds]
    return scs, seeds


def test_run_apcvfl_replicated_matches_sequential(replica_cells):
    scs, seeds = replica_cells
    kw = dict(max_epochs=3)
    seq = [pipeline.run_apcvfl(sc, seed=s, **kw)
           for sc, s in zip(scs, seeds)]
    rep = pipeline.run_apcvfl_replicated(scs, seeds=seeds, **kw)
    assert [r.seed for r in rep] == seeds
    for a, b in zip(seq, rep):
        # engine-level guarantees are exact / float-tolerance
        assert a.epochs == b.epochs
        assert a.comm == b.comm
        assert a.rounds == b.rounds and a.z_dim == b.z_dim
        assert _max_leaf_diff(a.params["g3"], b.params["g3"]) < 1e-4
        for k in a.metrics:
            assert abs(a.metrics[k] - b.metrics[k]) < METRIC_TOL, (k,)


def test_run_apcvfl_replicated_single_shared_scenario(replica_cells):
    """One scenario shared by every seed is the documented sugar; the
    seeds still differentiate init/splits so results differ."""
    scs, _ = replica_cells
    rep = pipeline.run_apcvfl_replicated(scs[0], seeds=[0, 1], max_epochs=2)
    assert len(rep) == 2
    assert rep[0].metrics != rep[1].metrics or \
        rep[0].epochs != rep[1].epochs


def test_run_apcvfl_is_the_one_seed_replicated_run(replica_cells):
    """``run_apcvfl`` is ``run_apcvfl_replicated`` with one seed: the same
    params, epochs, comm and metrics, exactly."""
    scs, seeds = replica_cells
    sc, s = scs[1], seeds[1]
    one = pipeline.run_apcvfl(sc, seed=s, max_epochs=3)
    (rep,) = pipeline.run_apcvfl_replicated(sc, seeds=[s], max_epochs=3)
    assert (one.epochs, one.comm, one.metrics) == (rep.epochs, rep.comm,
                                                   rep.metrics)
    assert jax.tree.structure(one.params) == jax.tree.structure(rep.params)
    for a, b in zip(jax.tree.leaves(one.params),
                    jax.tree.leaves(rep.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_aligned_only_replicated_matches_sequential(replica_cells):
    scs, seeds = replica_cells
    kw = dict(max_epochs=3, test_size=30)
    seq = [pipeline.run_apcvfl_aligned_only(sc, seed=s, **kw)
           for sc, s in zip(scs, seeds)]
    rep = pipeline.run_apcvfl_aligned_only_replicated(scs, seeds=seeds,
                                                      **kw)
    for a, b in zip(seq, rep):
        assert a.epochs == b.epochs and a.comm == b.comm
        assert _max_leaf_diff(a.params["g2"], b.params["g2"]) < 1e-4
        for k in a.metrics:
            assert abs(a.metrics[k] - b.metrics[k]) < METRIC_TOL, (k,)


def test_replicated_seed_scenario_count_mismatch_raises(replica_cells):
    scs, _ = replica_cells
    with pytest.raises(ValueError, match="scenarios for"):
        pipeline.run_apcvfl_replicated(scs, seeds=[0], max_epochs=2)


def test_run_apcvfl_replicated_use_kernel_runs_lanes(replica_cells):
    """ROADMAP follow-up: use_kernel seed groups used to fall back to
    sequential protocol runs.  With the kernel's custom VJP the lanes
    path trains the fused Eq. 5 directly and must match the sequential
    kernel path like any other replica run."""
    scs, seeds = replica_cells
    kw = dict(max_epochs=2, use_kernel=True)
    rep = pipeline.run_apcvfl_replicated(scs, seeds=seeds, **kw)
    seq = [pipeline.run_apcvfl(sc, seed=s, **kw)
           for sc, s in zip(scs, seeds)]
    for a, b in zip(seq, rep):
        assert a.epochs == b.epochs and a.comm == b.comm
        assert _max_leaf_diff(a.params["g3"], b.params["g3"]) < 1e-4
        for k in a.metrics:
            assert abs(a.metrics[k] - b.metrics[k]) < METRIC_TOL, (k,)


# ---------------------------------------------------------------------------
# K-party replica lanes (ROADMAP follow-up: run_apcvfl_k seed groups)
# ---------------------------------------------------------------------------

def test_run_apcvfl_k_replicated_matches_sequential():
    from repro.core import multiparty
    from repro.data.synthetic import make_dataset
    ds = make_dataset("bcw", seed=0)
    sc = multiparty.make_scenario_k(ds, n_parties=3, n_active_features=5,
                                    n_aligned=120, seed=0)
    seeds = [0, 1]
    seq = [multiparty.run_apcvfl_k(sc, seed=s, max_epochs=2)
           for s in seeds]
    rep = multiparty.run_apcvfl_k_replicated(sc, seeds=seeds, max_epochs=2)
    assert [r.seed for r in rep] == seeds
    for a, b in zip(seq, rep):
        assert a.epochs == b.epochs           # incl. per-passive g1 lanes
        assert a.comm == b.comm               # K-1 links, byte-identical
        assert a.rounds == b.rounds and a.z_dim == b.z_dim
        assert _max_leaf_diff(a.params["g3"], b.params["g3"]) < 1e-4
        for k in a.metrics:
            assert abs(a.metrics[k] - b.metrics[k]) < METRIC_TOL, (k,)


def test_sweep_kparty_seed_groups_use_replica_dispatch(monkeypatch):
    """A K>2 seed group must route through run_apcvfl_k_replicated (one
    lanes dispatch), not the sequential per-seed fallback."""
    from repro.core import multiparty
    calls = {"n": 0}
    real = multiparty.run_apcvfl_k_replicated

    def spy(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(multiparty, "run_apcvfl_k_replicated", spy)
    spec = ExperimentSpec(
        name="k-replica", dataset="bcw", aligned=(120,), n_parties=(3,),
        seeds=(0, 1), methods=(MethodSpec("apcvfl"),),
        overrides={"max_epochs": 1})
    results = sweep(spec)
    assert calls["n"] == 1                     # whole group, one dispatch
    assert [r.seed for r in results] == [0, 1]


# ---------------------------------------------------------------------------
# sweep-layer parity: the acceptance grid (2 methods x 2 aligned x 3 seeds)
# ---------------------------------------------------------------------------

def test_sweep_replicated_matches_sequential_acceptance_grid():
    spec = ExperimentSpec(
        name="replica-parity", dataset="bcw", aligned=(120, 100),
        seeds=(0, 1, 2),
        methods=(MethodSpec("local"), MethodSpec("apcvfl")),
        overrides={"max_epochs": 2})
    rep = sweep(spec)
    seq = sweep(dataclasses.replace(spec, replicate=False))
    # identical run order and coordinates regardless of dispatch path
    assert [(r.method, r.seed, tuple(sorted(r.scenario.items())))
            for r in rep] == \
           [(r.method, r.seed, tuple(sorted(r.scenario.items())))
            for r in seq]
    for a, b in zip(rep, seq):
        assert a.comm == b.comm and a.epochs == b.epochs
        for k in a.metrics:
            assert abs(a.metrics[k] - b.metrics[k]) < METRIC_TOL, \
                (a.method, a.seed, k)


# ---------------------------------------------------------------------------
# registry surface
# ---------------------------------------------------------------------------

def test_supports_replicas_flags():
    assert get_method("apcvfl").supports_replicas
    assert get_method("apcvfl_aligned_only").supports_replicas
    for name in ("local", "splitnn", "vfedtrans", "inversion"):
        assert not get_method(name).supports_replicas


def test_register_replicas_errors():
    with pytest.raises(KeyError, match="not registered"):
        register_replicas("no_such_method")(lambda *a, **k: [])
    with pytest.raises(ValueError, match="already has a replicated"):
        register_replicas("apcvfl")(lambda *a, **k: [])
    # the entry stays frozen data
    assert isinstance(get_method("apcvfl"), MethodEntry)


def test_replicated_runner_result_count_checked(monkeypatch):
    """A replicated runner returning the wrong number of results is a
    loud error, not silently misattributed seeds."""
    import repro.experiments.registry as reg
    entry = reg._REGISTRY["apcvfl"]
    monkeypatch.setitem(
        reg._REGISTRY, "apcvfl",
        dataclasses.replace(entry, replicated_fn=lambda sc, m, seeds: []))
    spec = ExperimentSpec(
        name="bad-rep", dataset="bcw", aligned=(100,), seeds=(0, 1),
        methods=(MethodSpec("apcvfl"),), overrides={"max_epochs": 1})
    with pytest.raises(RuntimeError, match="returned 0 results"):
        sweep(spec)


# ---------------------------------------------------------------------------
# the inversion attack as a registered method
# ---------------------------------------------------------------------------

def test_inversion_method_runs_from_spec():
    spec = ExperimentSpec(
        name="privacy", dataset="bcw", aligned=(150,), seeds=(0,),
        methods=(MethodSpec("inversion", params={"n_aux": 30}),
                 MethodSpec("inversion", label="inversion-rich",
                            params={"n_aux": 300})),
        overrides={"max_epochs": 20})
    results = sweep(spec)
    assert [r.method for r in results] == ["inversion", "inversion-rich"]
    for r in results:
        assert {"r2_mean", "attack_mse", "baseline_mse"} <= set(r.metrics)
        assert r.rounds == 1                  # rides on the one exchange
        assert r.comm["uplink_bytes"] > 0
    # more auxiliary pairs leak at least as much (paper-sharpening claim)
    assert results[1].metrics["r2_mean"] >= results[0].metrics["r2_mean"]
