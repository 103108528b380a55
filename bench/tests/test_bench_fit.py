"""The protocol-fit cell at a small size on the CPU: a sound run is
correct; the control (the reference in bfloat16) and every fault the cell
can have are not."""
import pytest

from cells import run_small

CELL = "mimic3_fig8.fit"


def test_sound_run_is_correct():
    out = run_small(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"fit_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_control_is_not_correct():
    out = run_small(CELL, control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "exchange_dropped", "probe_unchanged"])
def test_fault_is_not_correct(fault):
    out = run_small(CELL, fault=fault)
    assert not out["correct"], (fault, out["checks"])
