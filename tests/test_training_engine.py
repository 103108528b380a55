"""Tests for the device-resident scan training engine: the stored-trace
oracle (committed loss trajectory), early stopping, epoch callbacks,
compilation caching, and the comm wire-size fix that rides along."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autoencoder as ae
from repro.core import comm
from repro.core import distill
from repro.core import training

TRACE_PATH = pathlib.Path(__file__).parent / "data" / "train_trace.json"


def _toy(n=256, d=12, seed=0):
    x = np.random.RandomState(seed).randn(n, d).astype(np.float32)
    params = ae.init_autoencoder(jax.random.PRNGKey(seed), [d, 16, 8])
    return params, {"x": x}


def _max_leaf_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# stored-trace oracle: the committed trajectory of the (now retired) live
# parity runs.  Any semantic change to the host split, device permutation,
# loss, or Adam math moves these losses far beyond float noise.
# ---------------------------------------------------------------------------

def _trace_runs():
    """The oracle workloads.  ``tests/make_train_trace.py`` replays exactly
    these to (re)generate ``tests/data/train_trace.json``."""
    runs = {}
    params, data = _toy()
    # one full batch/epoch: row order inside the batch cannot matter
    runs["full_batch"] = (params, data,
                          dict(batch_size=10_000, max_epochs=8, patience=8,
                               seed=3))
    params, data = _toy(n=200, d=8, seed=1)
    # n_tr = 180, divisible by 36 -> 5 steps/epoch, real mini-batch path
    runs["minibatch"] = (params, data,
                         dict(batch_size=36, max_epochs=12, patience=12,
                              seed=1))
    return runs


def test_engine_matches_stored_trace():
    trace = json.loads(TRACE_PATH.read_text())
    # the initial parameters come from jax.random, whose bit layout may
    # change between jax releases: a mismatch names both versions
    versions = (f"trace made with jax {trace['jax_version']}, "
                f"running jax {jax.__version__}")
    for name, (params, data, kw) in _trace_runs().items():
        r = training.train(params, data, ae.recon_loss, **kw)
        want = trace["runs"][name]
        msg = f"{name} ({versions})"
        assert r.epochs_run == want["epochs_run"], msg
        assert r.steps_run == want["steps_run"], msg
        np.testing.assert_allclose(r.train_loss, want["train_loss"],
                                   rtol=2e-3, atol=1e-5, err_msg=msg)
        np.testing.assert_allclose(r.val_loss, want["val_loss"],
                                   rtol=2e-3, atol=1e-5, err_msg=msg)


def test_scan_drops_remainder():
    """Static batch shapes: the epoch runs n_tr // bs full batches and
    drops the remainder rows of the permutation."""
    params, data = _toy(n=110, d=4)     # n_tr = 99, bs 32 -> 3 full + 3 rest
    kw = dict(batch_size=32, max_epochs=2, patience=99, seed=0)
    assert training.train(params, data, ae.recon_loss, **kw).steps_run == 6


# ---------------------------------------------------------------------------
# early stopping + histories
# ---------------------------------------------------------------------------

def test_early_stopping_on_plateau():
    """lr=0 never improves after the first epoch's best, so training stops
    after exactly patience further epochs."""
    params, data = _toy(n=64, d=4)
    r = training.train(params, data, ae.recon_loss, batch_size=16,
                       max_epochs=50, patience=3, lr=0.0, seed=0)
    assert r.epochs_run == 1 + 3
    assert len(r.train_loss) == len(r.val_loss) == r.epochs_run
    # with lr=0 params never move: best == initial
    assert _max_leaf_diff(r.params, params) == 0.0


def test_best_params_returned_not_last():
    """The returned params are the best-val snapshot, immune to the
    engine's buffer donation in later epochs."""
    params, data = _toy(n=128, d=6, seed=2)
    seen = []
    r = training.train(params, data, ae.recon_loss, batch_size=32,
                       max_epochs=8, patience=99, seed=2,
                       epoch_callback=lambda e, p, tl, vl: seen.append(vl))
    best_epoch = int(np.argmin(r.val_loss))
    assert r.val_loss[best_epoch] == min(seen)
    # snapshot buffers are alive and usable after training returned
    assert np.isfinite(np.asarray(ae.encode(r.params,
                                            jnp.asarray(data["x"][:4])))).all()


def test_epoch_callback_params_survive_donation():
    """Regression: callback params must be defensive copies — stashing them
    across epochs and reading them after training used to hit the engine's
    donated (deleted) buffers."""
    params, data = _toy(n=96, d=5)
    stashed = []
    r = training.train(params, data, ae.recon_loss, batch_size=32,
                       max_epochs=4, patience=99, seed=0,
                       epoch_callback=lambda e, p, tl, vl: stashed.append(p))
    assert len(stashed) == r.epochs_run
    for p in stashed:   # every stashed snapshot still readable post-training
        z = np.asarray(ae.encode(p, jnp.asarray(data["x"][:3])))
        assert np.isfinite(z).all()
    # snapshots are distinct per epoch, not one aliased buffer
    assert _max_leaf_diff(stashed[0], stashed[-1]) > 0.0


def test_epoch_callback_invoked_per_epoch():
    params, data = _toy(n=96, d=5)
    calls = []

    def cb(epoch, p, tl, vl):
        # params must be usable synchronously (donated next epoch)
        z = ae.encode(p, jnp.asarray(data["x"][:2]))
        calls.append((epoch, float(jnp.sum(z)), tl, vl))

    r = training.train(params, data, ae.recon_loss, batch_size=32,
                       max_epochs=5, patience=99, seed=0, epoch_callback=cb)
    assert [c[0] for c in calls] == list(range(r.epochs_run))
    assert all(np.isfinite(c[1:]).all() for c in [np.asarray(c[1:])
                                                  for c in calls])


# ---------------------------------------------------------------------------
# compilation caching: make_loss closures share one engine
# ---------------------------------------------------------------------------

def test_make_loss_closures_share_compiled_engine():
    l1 = distill.make_loss(lam=0.07, kind="mae")
    l2 = distill.make_loss(lam=0.07, kind="mae")
    l3 = distill.make_loss(lam=0.08, kind="mae")
    assert l1 is not l2
    assert training.get_engine(l1) is training.get_engine(l2)
    assert training.get_engine(l1) is not training.get_engine(l3)
    assert training.get_fit_engine(l1) is training.get_fit_engine(l2)
    assert training.get_fit_engine(l1) is not training.get_fit_engine(l3)
    # epochwise and fused engines live under distinct cache tags
    assert training.get_fit_engine(l1) is not training.get_engine(l1)


def test_no_recompilation_across_make_loss_instances():
    """Two make_loss() closures with equal hyperparameters and equal data
    shapes must hit the same jit cache entry (zero new compilations)."""
    d, m = 6, 4
    x = np.random.RandomState(0).randn(120, d).astype(np.float32)
    data = {"x": x, "z_teacher": np.zeros((120, m), np.float32),
            "aligned": np.ones((120,), np.float32)}
    params = ae.init_autoencoder(jax.random.PRNGKey(0), [d, 8, m])
    kw = dict(batch_size=32, max_epochs=2, patience=99, seed=0)

    engine = training.get_fit_engine(distill.make_loss(lam=0.11))
    if not hasattr(engine, "_cache_size"):   # private jax API; guard it
        pytest.skip("this jax version has no PjitFunction._cache_size")
    training.train(params, data, distill.make_loss(lam=0.11), **kw)
    misses = engine._cache_size()
    assert misses >= 1
    training.train(params, data, distill.make_loss(lam=0.11), **kw)
    assert engine._cache_size() == misses   # no fresh compilation


# ---------------------------------------------------------------------------
# fused scan-of-scans engine vs the epochwise parity oracle
# ---------------------------------------------------------------------------

def test_fused_matches_epochwise_on_trace_workloads():
    """The fused whole-fit engine must reproduce the per-epoch-loop engine
    EXACTLY on the stored-trace workloads: same early-stop epoch count,
    same step count, float-identical histories and best-val params (both
    paths run the identical per-epoch computation; only the early-stop
    bookkeeping moved on device)."""
    for name, (params, data, kw) in _trace_runs().items():
        fused = training.train(params, data, ae.recon_loss, **kw)
        loop = training.train_epochwise(params, data, ae.recon_loss, **kw)
        assert fused.epochs_run == loop.epochs_run, name
        assert fused.steps_run == loop.steps_run, name
        np.testing.assert_allclose(fused.train_loss, loop.train_loss,
                                   rtol=1e-6, err_msg=name)
        np.testing.assert_allclose(fused.val_loss, loop.val_loss,
                                   rtol=1e-6, err_msg=name)
        assert _max_leaf_diff(fused.params, loop.params) < 1e-6, name


def test_fused_matches_epochwise_early_stop():
    """Early-stop epoch counts agree on a genuinely-stopping workload."""
    params, data = _toy(n=64, d=4)
    kw = dict(batch_size=16, max_epochs=50, patience=3, lr=0.0, seed=0)
    fused = training.train(params, data, ae.recon_loss, **kw)
    loop = training.train_epochwise(params, data, ae.recon_loss, **kw)
    assert fused.epochs_run == loop.epochs_run == 1 + 3
    assert _max_leaf_diff(fused.params, loop.params) == 0.0


def test_fused_lanes_match_epochwise_lanes():
    """train_lanes (fused) vs train_lanes_epochwise on uneven lanes:
    exact epoch counts, float-identical params and histories per lane."""
    specs = []
    for i, (n, d) in enumerate([(120, 6), (90, 4), (150, 5)]):
        x = np.random.RandomState(10 + i).randn(n, d).astype(np.float32)
        p = ae.init_autoencoder(jax.random.PRNGKey(20 + i), [d, 8, 4])
        specs.append(training.LaneSpec(p, {"x": x}, seed=i))
    kw = dict(batch_size=16, max_epochs=25, patience=4)
    fused = training.train_lanes(specs, ae.masked_recon_loss, **kw)
    loop = training.train_lanes_epochwise(specs, ae.masked_recon_loss, **kw)
    for i, (f, l) in enumerate(zip(fused, loop)):
        assert f.epochs_run == l.epochs_run, i
        assert f.steps_run == l.steps_run, i
        np.testing.assert_allclose(f.train_loss, l.train_loss, rtol=1e-6)
        np.testing.assert_allclose(f.val_loss, l.val_loss, rtol=1e-6)
        assert _max_leaf_diff(f.params, l.params) < 1e-6, i


def test_fused_fit_is_single_dispatch(monkeypatch):
    """<=1 host sync per fit: the whole fit goes through exactly one call
    of the fused engine (the epoch loop lives inside the jitted scan)."""
    params, data = _toy(n=120, d=5)
    calls = []
    real = training.get_fit_engine

    def spy(loss_fn, *, lr=1e-3):
        engine = real(loss_fn, lr=lr)

        def wrapped(*a, **k):
            calls.append(k.get("max_epochs"))
            return engine(*a, **k)
        return wrapped

    monkeypatch.setattr(training, "get_fit_engine", spy)
    r = training.train(params, data, ae.recon_loss, batch_size=32,
                       max_epochs=9, patience=99, seed=0)
    assert r.epochs_run == 9
    assert calls == [9]


def test_fused_lanes_fit_is_single_dispatch(monkeypatch):
    params, data = _toy(n=120, d=5)
    calls = []
    real = training.get_lanes_fit_engine

    def spy(loss_fn, *, lr=1e-3):
        engine = real(loss_fn, lr=lr)

        def wrapped(*a, **k):
            calls.append(k.get("max_epochs"))
            return engine(*a, **k)
        return wrapped

    monkeypatch.setattr(training, "get_lanes_fit_engine", spy)
    rs = training.train_lanes(
        [training.LaneSpec(params, data, 0),
         training.LaneSpec(params, data, 1)],
        ae.masked_recon_loss, batch_size=32, max_epochs=7, patience=99)
    assert [r.epochs_run for r in rs] == [7, 7]
    assert calls == [7]


# ---------------------------------------------------------------------------
# comm: wire size follows the dtype, analytic formulas stay float32
# ---------------------------------------------------------------------------

def test_send_array_uses_dtype_itemsize():
    ch = comm.Channel()
    ch.send_array("f32", np.zeros((10, 3), np.float32))
    ch.send_array("f64", np.zeros((10, 3), np.float64))
    ch.send_array("f16", jnp.zeros((8,), jnp.float16))
    assert ch.log[0][1] == 30 * 4
    assert ch.log[1][1] == 30 * 8
    assert ch.log[2][1] == 8 * 2
