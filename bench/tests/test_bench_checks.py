"""The comparison that decides ``correct`` reads a gap that is not finite
as infinite, in any lane or reading, not only the first."""
import dataclasses

import numpy as np
import pytest

from cells import run_small

import checks

NAN = float("nan")


def test_rel_reads_a_nan_on_either_side_as_infinite():
    assert checks.rel(NAN, 1.0) == float("inf")
    assert checks.rel(1.0, NAN) == float("inf")
    assert checks.rel(1.1, 1.0) == pytest.approx(0.1)


def test_change_gap_reads_a_nan_leaf_as_infinite():
    init = {"w": np.zeros(3), "b": np.zeros(2)}
    ref = {"w": np.ones(3), "b": np.ones(2)}
    prog = {"w": np.array([1.0, NAN, 1.0]), "b": np.ones(2)}
    assert checks.change_gap(prog, ref, init) == float("inf")
    assert checks.change_gap(ref, ref, init) == 0.0


@pytest.mark.parametrize("bad", [NAN, float("inf")])
def test_judge_fails_a_non_finite_reading_after_the_first(bad):
    readings = [{"g1_epoch1": 0.001}, {"g1_epoch1": bad}]
    out = checks.judge(readings, {"g1_epoch1": 0.04})
    assert out["g1_epoch1"]["value"] > out["g1_epoch1"]["limit"]


def test_lane_numbers_read_a_nan_loss_as_infinite():
    ref = {"train_loss": [1.0, 0.5], "val_loss": [1.0, 0.5],
           "params": {"w": np.ones(2)}}
    prog = {"train_loss": [1.0, NAN], "val_loss": [1.0, NAN],
            "params": {"w": np.ones(2)}}
    out = checks.lane_numbers(prog, ref, {"w": np.zeros(2)})
    assert out["final_val_loss"] == float("inf")


def test_a_lane_that_diverges_after_the_first_is_not_correct(monkeypatch):
    """The last lane of every lane-engine call returns NaN losses and
    weights, as a fit that overflowed would; the first lane is sound."""
    from repro.core import training
    orig = training.train_lanes

    def diverged(specs, loss_fn, **kw):
        out = list(orig(specs, loss_fn, **kw))
        last = out[-1]
        out[-1] = dataclasses.replace(
            last, train_loss=[NAN] * len(last.train_loss),
            val_loss=[NAN] * len(last.val_loss))
        return out

    monkeypatch.setattr(training, "train_lanes", diverged)
    out = run_small("mimic3_fig8.fit")
    assert not out["correct"], out["checks"]
    assert max(c["value"] for c in out["checks"].values()) == 1e300
