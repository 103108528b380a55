"""The lane-sharded cell at a small size on four CPU devices, in a child
process (the device count is fixed when JAX starts): a sound run is
correct; the control and every fault the cell can have are not."""
import json
import os
import subprocess
import sys

import pytest

from cells import BENCH

CELL = "scale_1m_k8.fit4"
CHILD = f"""
import json, sys
sys.path.insert(0, {str(BENCH / "tests")!r})
from cells import run_small
out = {{}}
for case in sys.argv[1:]:
    kind, _, fault = case.partition(":")
    r = run_small({CELL!r}, control=kind == "control", fault=fault)
    out[case] = {{"correct": r["correct"], "checks": r["checks"]}}
print(json.dumps(out))
"""
CASES = ["program", "control", "program:state_unchanged",
         "program:half_batch", "program:chip_exchange_dropped"]


@pytest.fixture(scope="module")
def outcomes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD, *CASES], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(outcomes):
    assert outcomes["program"]["correct"], outcomes["program"]


@pytest.mark.parametrize("case", CASES[1:])
def test_control_and_faults_are_not_correct(outcomes, case):
    assert not outcomes[case]["correct"], outcomes[case]
