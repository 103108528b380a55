"""The lane engine's epoch batch order: each epoch's batches are sliced
straight from the lane's permutation of its train rows, with no
partition of that permutation in the compiled program, and ``_prep_lanes``
refuses a stack whose lanes do not share their train rows (the invariant
the slice rests on)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autoencoder as ae
from repro.core import training

L, N_TR = 4, 900            # 1000 rows a lane, 100 held out for validation

# ``%sort.0 = (u32[4,900]{1,0}, s32[4,900]{1,0}) sort(...)``: the key dtype
# is the first element of the result
_SORT = re.compile(r"=\s*\(?(\w+)\[[\d,]*\][^=]*?\ssort\(")
# ``%gather.11 = s32[3600,1,1]{2,1,0} gather(...)``
_GATHER = re.compile(r"=\s*(\w+)\[([\d,]*)\]\S*\s+gather\(")


def _specs(rows, widths=None, seed=0):
    widths = widths or [6] * len(rows)
    return [training.LaneSpec(
        ae.init_autoencoder(jax.random.PRNGKey(seed + i), [d, 8, 4]),
        {"x": np.random.RandomState(seed + i).randn(n, d).astype(np.float32)},
        seed=seed + i) for i, (n, d) in enumerate(zip(rows, widths))]


def _lowered_engine(kind):
    params, opt, keys, tr, val, n_batches, bs = training._prep_lanes(
        _specs([N_TR + 100] * L), batch_size=64, val_frac=0.1, lr=1e-3)
    assert tr["x"].shape[:2] == (L, N_TR)
    live = jnp.ones((L,), bool)
    kw = dict(n_batches=n_batches, batch_size=bs, patience=2)
    if kind == "fit":
        engine = training.get_lanes_fit_engine(ae.masked_recon_loss)
        return engine.lower(params, opt, keys, tr, val, live, max_epochs=3,
                            **kw)
    engine = training.get_lanes_engine(ae.masked_recon_loss)
    carry = training._carry0(params, opt, params, live)
    return engine.lower(carry, 0, keys, tr, val, **kw)


def _compiled_engine_text(kind):
    return _lowered_engine(kind).compile().as_text()


@pytest.mark.parametrize("kind", ["fit", "epochwise"])
def test_engine_epoch_has_no_permutation_partition(kind):
    text = _compiled_engine_text(kind)
    sort_keys = _SORT.findall(text)
    # the permutation's own shuffle rounds remain, each keyed on u32 bits
    # (their count follows N through jax.random's number of rounds)
    assert sort_keys, "the epoch permutation's sort is missing"
    assert set(sort_keys) == {"u32"}, sort_keys
    # no gather of the stacked permutation into a new s32[L*N] order
    perm_gathers = [shape for dtype, shape in _GATHER.findall(text)
                    if dtype == "s32"
                    and np.prod([int(s) for s in shape.split(",") if s])
                    == L * N_TR]
    assert not perm_gathers, perm_gathers


def test_program_names_tell_the_scan_from_the_epoch():
    """The device trace finds the whole-fit scan by the substring
    ``run_fit_k`` in its module name; the epoch program must not match."""
    names = {kind: re.search(r"module @(\S+)",
                             _lowered_engine(kind).as_text()).group(1)
             for kind in ("fit", "epochwise")}
    assert "run_fit_k" in names["fit"], names
    assert "run_fit_k" not in names["epochwise"], names
    assert "run_epoch_k" in names["epochwise"], names


def test_prep_lanes_refuses_unequal_train_rows():
    with pytest.raises(ValueError, match="unequal train rows"):
        training._prep_lanes(_specs([120, 150]), batch_size=16,
                             val_frac=0.1, lr=1e-3)


def test_prep_lanes_accepts_equal_train_rows_with_unequal_val_rows():
    # 19 and 20 rows both hold out 1-2 rows and train on 18: only the
    # validation stack is row-padded, and its padded rows weigh zero
    params, opt, keys, tr, val, n_batches, bs = training._prep_lanes(
        _specs([19, 20]), batch_size=8, val_frac=0.1, lr=1e-3)
    assert tr["x"].shape[:2] == (2, 18)
    assert n_batches == 2 and bs == 8
    np.testing.assert_array_equal(np.asarray(val["row_w"]),
                                  [[1.0, 0.0], [1.0, 1.0]])


def test_mixed_shape_fleet_groups_and_matches_solo_runs():
    """Lanes of three row counts and widths go through ``train_lanes`` as
    shape groups; with the batch under every lane's train rows, each lane
    draws its solo run's batches and matches it."""
    specs = _specs([120, 90, 150, 120], widths=[6, 4, 5, 6], seed=30)
    kw = dict(batch_size=16, max_epochs=6, patience=6)
    assert training._lane_groups(specs) == [[0, 3], [1], [2]]
    lanes = training.train_lanes(specs, ae.masked_recon_loss, **kw)
    loop = training.train_lanes_epochwise(specs, ae.masked_recon_loss, **kw)
    for sp, lane, ref in zip(specs, lanes, loop):
        solo = training.train(sp.params, sp.data, ae.recon_loss,
                              seed=sp.seed, **kw)
        n_tr = len(sp.data["x"]) - max(int(len(sp.data["x"]) * 0.1), 1)
        assert lane.steps_run == lane.epochs_run * (n_tr // 16)
        for other in (solo, ref):
            assert (lane.epochs_run, lane.steps_run) == (other.epochs_run,
                                                         other.steps_run)
            np.testing.assert_allclose(lane.val_loss, other.val_loss,
                                       rtol=1e-5, atol=1e-6)
            for a, b in zip(jax.tree.leaves(lane.params),
                            jax.tree.leaves(other.params)):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
