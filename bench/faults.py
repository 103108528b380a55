"""Faults planted under the timed path, to show that ``correct`` catches
them (``tests/test_bench_faults.py`` on the CPU; ``calibrate.py --fault``
on the chip, for the readings the limits were set from).  Never used by a
benchmark run.

- ``state_unchanged``: every optimizer step returns the weights and the
  optimizer state it was given;
- ``half_batch``: every training step keeps the first half of its batch
  and takes the mean over it;
- ``exchange_dropped``: the passive party's exchanged latents arrive with
  their second half of rows zeroed;
- ``probe_unchanged``: every step of the k-fold probe's optimizer returns
  the probe's weights and state it was given (the lane engine is sound);
- ``chip_exchange_dropped``: lanes that go to any chip but the first
  arrive there as zeros.
"""
from __future__ import annotations

import contextlib

ROW_KEYS = ("x", "z_teacher", "aligned", "row_w")


def _half(batch: dict) -> dict:
    out = dict(batch)
    for k in ROW_KEYS:
        if k in out:
            out[k] = out[k][: out[k].shape[0] // 2]
    return out


@contextlib.contextmanager
def plant(name: str):
    import jax
    import jax.numpy as jnp

    from repro.core import autoencoder as ae
    from repro.core import classifier, comm, distill, training

    undo = []

    def patch(obj, attr, value):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)

    def frozen(module):
        orig = module.paper_adam

        class Frozen(type(orig())):
            def update(self, grads, state, params):
                return params, state, jnp.zeros((), jnp.float32)

        patch(module, "paper_adam", lambda lr=1e-3: Frozen(lr=lr))

    if name == "state_unchanged":
        frozen(training)
    elif name == "probe_unchanged":
        frozen(classifier)
    elif name == "half_batch":
        recon = ae.masked_recon_loss
        lanes_loss = distill.make_lanes_loss

        def half_recon(params, batch):
            return recon(params, _half(batch))

        def half_lanes(*a, **k):
            inner = lanes_loss(*a, **k)

            def loss(params, batch):
                return inner(params, _half(batch))
            return loss

        patch(ae, "masked_recon_loss", half_recon)
        patch(distill, "make_lanes_loss", half_lanes)
    elif name == "exchange_dropped":
        orig = comm.exchange_array

        def dropped(*a, **k):
            z = orig(*a, **k)
            return z.at[z.shape[0] // 2:].set(0.0)

        patch(comm, "exchange_array", dropped)
    elif name == "chip_exchange_dropped":
        orig = training._shard_lanes

        def dropped_lanes(mesh, *args, **kw):
            out = orig(mesh, *args, **kw)
            tr = dict(out[3])
            x = tr["x"]
            keep = x.shape[0] // mesh.devices.size
            tr["x"] = jax.device_put(x.at[keep:].set(0.0), x.sharding)
            return out[:3] + (tr,) + out[4:]

        patch(training, "_shard_lanes", dropped_lanes)
    else:
        raise KeyError(f"unknown fault {name!r}")
    saved = dict(training._ENGINE_CACHE)
    training._ENGINE_CACHE.clear()
    classifier._fit_predict_folds_many.clear_cache()
    try:
        yield
    finally:
        for obj, attr, value in reversed(undo):
            setattr(obj, attr, value)
        training._ENGINE_CACHE.clear()
        training._ENGINE_CACHE.update(saved)
        classifier._fit_predict_folds_many.clear_cache()
