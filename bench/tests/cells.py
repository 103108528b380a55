"""Small versions of the benchmark's cells for tests on the CPU: the real
``BENCHMARK.json`` entries, configurations, traffic mixes and limits, with
only the amount of work cut (epochs, lanes, rows)."""
from __future__ import annotations

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

SMALL = {
    "mimic3_fig8.fit": lambda s: (
        s["config"]["train"].update(max_epochs=2),
        s["config"].update(patience=2),
        s["traffic"].update(seed_lanes_per_fit=2, check_lanes=2)),
    "scale_1m_k8.fit4": lambda s: (
        s["config"].update(rows=20000, parties=2, seed_replicas=2,
                           batch_size=512, max_epochs=2, patience=2),
        s["traffic"].update(check_lanes_per_chip=1)),
}


def small_spec(name: str) -> dict:
    spec = run.load_cell(name)
    SMALL[name](spec)
    return spec


def run_small(name: str, *, seed: int = 5, control: bool = False,
              fault: str = "") -> dict:
    """One run of the small cell on the CPU's devices, optionally with the
    control in the program's place or a fault planted in the program."""
    import contextlib

    import jax

    import faults

    spec = small_spec(name)
    devices = jax.devices()[:spec["cell"]["chips"]]
    with faults.plant(fault) if fault else contextlib.nullcontext():
        return run.run_cell(name, seed, 0.01, False, devices=devices,
                            spec=spec, control=control)
