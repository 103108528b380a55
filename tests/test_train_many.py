"""Tests for K-party fits as lanes of one engine (``training.train_lanes``):
parity with K independent ``training.train`` calls, uneven feature widths and
heterogeneous architectures (padded-stack layout), uneven row counts, and
per-party early stopping at different epochs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autoencoder as ae
from repro.core import training


def _toy(n, d, seed, widths=None):
    x = np.random.RandomState(seed).randn(n, d).astype(np.float32)
    params = ae.init_autoencoder(jax.random.PRNGKey(seed),
                                 widths or [d, 16, 8])
    return params, {"x": x}


def _max_leaf_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _solo(params, data, seed, **kw):
    return training.train(params, data, ae.recon_loss, seed=seed, **kw)


def test_masked_recon_loss_equals_recon_loss_without_padding():
    params, data = _toy(32, 6, 0)
    x = jnp.asarray(data["x"])
    batch = {"x": x, "mask": jnp.ones((6,)), "row_w": jnp.ones((32,))}
    a = float(ae.recon_loss(params, {"x": x}))
    b = float(ae.masked_recon_loss(params, batch))
    assert abs(a - b) < 1e-6


def test_parity_uneven_widths_equal_rows():
    """Equal row counts -> every party draws the IDENTICAL device
    permutation as its solo run, so params/losses/epoch counts match the
    sequential path to float tolerance despite the feature padding."""
    kw = dict(batch_size=36, max_epochs=8, patience=8)
    specs, solos = [], []
    for i, d in enumerate([5, 9, 7]):
        params, data = _toy(200, d, i)
        specs.append(training.LaneSpec(params, data, seed=i))
        solos.append(_solo(params, data, i, **kw))
    many = training.train_lanes(specs, ae.masked_recon_loss, **kw)
    for s, m in zip(solos, many):
        assert (s.epochs_run, s.steps_run) == (m.epochs_run, m.steps_run)
        np.testing.assert_allclose(s.train_loss, m.train_loss, atol=1e-4)
        np.testing.assert_allclose(s.val_loss, m.val_loss, atol=1e-4)
        assert _max_leaf_diff(s.params, m.params) < 1e-4


def test_parity_heterogeneous_architectures():
    """g1_active-style and g1_passive-style parties (different hidden AND
    latent widths) stack into one batch: zero-padded weights feed on zero
    inputs and get zero gradients, so each real sub-block still matches its
    solo run."""
    kw = dict(batch_size=32, max_epochs=6, patience=6)
    p1, d1 = _toy(160, 6, 0, widths=[6, 8, 16])
    p2, d2 = _toy(160, 11, 1, widths=[11, 16, 32])
    s1, s2 = _solo(p1, d1, 0, **kw), _solo(p2, d2, 1, **kw)
    m1, m2 = training.train_lanes(
        [training.LaneSpec(p1, d1, 0), training.LaneSpec(p2, d2, 1)],
        ae.masked_recon_loss, **kw)
    for s, m in zip((s1, s2), (m1, m2)):
        assert [l.shape for l in jax.tree.leaves(s.params)] == \
            [l.shape for l in jax.tree.leaves(m.params)]
        assert _max_leaf_diff(s.params, m.params) < 1e-4


def test_uneven_row_counts_statistical_parity():
    """Row-padded parties draw a filtered permutation (different batch
    order than solo) but must land in the same val-loss neighbourhood with
    per-party step accounting intact."""
    kw = dict(batch_size=32, max_epochs=8, patience=8)
    p1, d1 = _toy(150, 6, 0)
    p2, d2 = _toy(260, 6, 1)
    s1, s2 = _solo(p1, d1, 0, **kw), _solo(p2, d2, 1, **kw)
    m1, m2 = training.train_lanes(
        [training.LaneSpec(p1, d1, 0), training.LaneSpec(p2, d2, 1)],
        ae.masked_recon_loss, **kw)
    # party 2 is unpadded (max rows) -> exact parity incl. step counts
    assert (s2.epochs_run, s2.steps_run) == (m2.epochs_run, m2.steps_run)
    assert _max_leaf_diff(s2.params, m2.params) < 1e-4
    # party 1 is row-padded -> its own step budget, statistical parity
    assert m1.steps_run == m1.epochs_run * (135 // 32)
    assert abs(s1.val_loss[-1] - m1.val_loss[-1]) < 0.1 * max(
        s1.val_loss[-1], 1e-3)


def test_per_party_early_stopping_at_different_epochs():
    """A near-constant-data party plateaus and stops well before a
    random-data party; each party's stop epoch must match its solo run and
    its histories truncate at its own stop."""
    kw = dict(batch_size=25, max_epochs=40, patience=3)
    rng = np.random.RandomState(0)
    d_easy = {"x": np.full((125, 4), 0.5, np.float32)
              + 1e-3 * rng.randn(125, 4).astype(np.float32)}
    p_easy = ae.init_autoencoder(jax.random.PRNGKey(0), [4, 8, 4])
    p_hard, d_hard = _toy(125, 4, 1, widths=[4, 8, 4])
    s_easy = _solo(p_easy, d_easy, 0, **kw)
    s_hard = _solo(p_hard, d_hard, 1, **kw)
    m_easy, m_hard = training.train_lanes(
        [training.LaneSpec(p_easy, d_easy, 0),
         training.LaneSpec(p_hard, d_hard, 1)],
        ae.masked_recon_loss, **kw)
    assert m_easy.epochs_run == s_easy.epochs_run
    assert m_hard.epochs_run == s_hard.epochs_run
    assert m_easy.epochs_run != m_hard.epochs_run
    for m in (m_easy, m_hard):
        assert len(m.train_loss) == len(m.val_loss) == m.epochs_run
    # the early-stopped party's best params match its solo run: frozen
    # stepping after its stop must not leak into the returned snapshot
    assert _max_leaf_diff(s_easy.params, m_easy.params) < 1e-4


def test_single_party_degenerates_to_train():
    kw = dict(batch_size=64, max_epochs=5, patience=5)
    params, data = _toy(128, 7, 3)
    s = _solo(params, data, 3, **kw)
    (m,) = training.train_lanes([training.LaneSpec(params, data, 3)],
                                ae.masked_recon_loss, **kw)
    assert (s.epochs_run, s.steps_run) == (m.epochs_run, m.steps_run)
    assert _max_leaf_diff(s.params, m.params) < 1e-4
