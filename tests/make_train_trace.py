"""(Re)generate the stored-trace oracle for the scan training engine.

Run from the repo root after an INTENTIONAL semantic change to the engine
(split, permutation, loss, or optimizer math)::

    PYTHONPATH=src:tests python tests/make_train_trace.py

The workloads replayed here are defined once, in
``tests/test_training_engine.py::_trace_runs`` — this script only records
what the engine produces, so generator and test can never drift apart.
The file also records the jax version it was made with: the initial
parameters come from ``jax.random``, whose bit layout can change between
jax releases (``jax_threefry_partitionable`` became the default in 0.5).
"""
import json
import pathlib
import sys

import jax

sys.path.insert(0, str(pathlib.Path(__file__).parent))

from test_training_engine import TRACE_PATH, _trace_runs  # noqa: E402

from repro.core import autoencoder as ae                  # noqa: E402
from repro.core import training                           # noqa: E402


def main() -> None:
    runs = {}
    for name, (params, data, kw) in _trace_runs().items():
        r = training.train(params, data, ae.recon_loss, **kw)
        runs[name] = {"epochs_run": r.epochs_run, "steps_run": r.steps_run,
                       "train_loss": r.train_loss, "val_loss": r.val_loss}
        print(f"{name}: {r.epochs_run} epochs, {r.steps_run} steps, "
              f"final val {r.val_loss[-1]:.6f}")
    TRACE_PATH.parent.mkdir(parents=True, exist_ok=True)
    trace = {"jax_version": jax.__version__, "runs": runs}
    TRACE_PATH.write_text(json.dumps(trace, indent=1) + "\n")
    print(f"wrote {TRACE_PATH}")


if __name__ == "__main__":
    main()
