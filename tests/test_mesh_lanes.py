"""Mesh-sharded lane engine tests: lane-mesh construction and validation,
sharded-vs-unsharded ``train_lanes`` parity (lane axis, row axis, and the
replicated pipeline on top), the ``ExperimentSpec.devices`` dispatch path,
and the streaming scale generator.

Multi-device tests are marked ``needs_devices(n)`` and auto-skip on the
default 1-device CPU; CI's multidevice job runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  Parity bands
follow ``tests/test_replicas.py``: engine-level outputs exact / float
tolerance, probe metrics a 0.03 CV-noise band.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autoencoder as ae
from repro.core import pipeline, training
from repro.core.training import LaneSpec
from repro.data import scale
from repro.experiments import ExperimentSpec, MethodSpec, sweep
from repro.experiments.specs import ScenarioSpec
from repro.experiments.sweeps import build_scenario
from repro.launch import mesh as meshlib

METRIC_TOL = 0.03


def _max_leaf_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# mesh construction + validation
# ---------------------------------------------------------------------------

def test_make_lane_mesh_axis_names():
    m = meshlib.make_lane_mesh(lane=1, data=1)
    assert m.axis_names == ("lane", "data")


def test_make_lane_mesh_too_many_devices_names_the_fix():
    want = jax.device_count() * 2
    with pytest.raises(ValueError) as ei:
        meshlib.make_lane_mesh(lane=want)
    msg = str(ei.value)
    assert f"needs {want} devices" in msg
    assert f"xla_force_host_platform_device_count={want}" in msg


def test_make_lane_mesh_spans_first_devices():
    """A mesh spans the first ``lane * data`` of ``jax.devices()``."""
    assert list(meshlib.make_lane_mesh(lane=1).devices.flat) \
        == [jax.devices()[0]]


def test_make_local_mesh_too_many_devices():
    with pytest.raises(ValueError, match="needs"):
        meshlib.make_local_mesh(data=jax.device_count() * 2)


@pytest.mark.parametrize("bad", [0, -1, 1.5, "2", None])
def test_make_lane_mesh_rejects_non_positive_axes(bad):
    with pytest.raises(ValueError, match="positive int"):
        meshlib.make_lane_mesh(lane=bad)


# ---------------------------------------------------------------------------
# sharded train_lanes parity
# ---------------------------------------------------------------------------

def _uneven_lanes(n_lanes=3):
    """Lanes with different row counts and widths — exercises both the
    per-lane zero padding and (on a mesh) the lane-axis padding to a
    device multiple (3 real lanes on a 4-device lane axis)."""
    rng = np.random.RandomState(0)
    shapes = [(120, 6), (90, 4), (150, 5)][:n_lanes]
    lanes = []
    for i, (n, d) in enumerate(shapes):
        x = jnp.asarray(rng.randn(n, d).astype(np.float32))
        params = ae.init_autoencoder(jax.random.PRNGKey(10 + i),
                                     [d, 8, 4])
        lanes.append(LaneSpec(params, {"x": x}, seed=i))
    return lanes


def _assert_lane_results_match(a, b, *, tol=1e-6):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra.epochs_run == rb.epochs_run
        assert ra.steps_run == rb.steps_run
        np.testing.assert_allclose(ra.train_loss, rb.train_loss,
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(ra.val_loss, rb.val_loss,
                                   rtol=1e-6, atol=1e-7)
        assert _max_leaf_diff(ra.params, rb.params) < tol


@pytest.mark.needs_devices(4)
def test_train_lanes_sharded_matches_unsharded():
    """Same jitted engine, inputs device_put across a 4-device lane axis
    (3 real lanes -> 1 dead padded lane): results must match the
    single-device run to float tolerance."""
    kw = dict(batch_size=16, max_epochs=6, patience=4, lr=1e-3)
    base = training.train_lanes(_uneven_lanes(), ae.masked_recon_loss,
                                **kw)
    m = meshlib.make_lane_mesh(lane=4)
    sharded = training.train_lanes(_uneven_lanes(), ae.masked_recon_loss,
                                   mesh=m, **kw)
    _assert_lane_results_match(base, sharded)


@pytest.mark.needs_devices(4)
def test_train_lanes_kernel_path_mesh_parity():
    """The fused lane-MLP kernel path (use_kernel=True; Pallas interpret
    mode on CPU) must shard across a 4-device lane mesh with the same
    parity as the jnp path: the vmap-prepended lane grid has to survive
    shard_map partitioning, dead padded lanes included."""
    kw = dict(batch_size=16, max_epochs=4, patience=3, lr=1e-3)
    loss = ae.make_masked_recon_loss(use_kernel=True)
    base = training.train_lanes(_uneven_lanes(), loss, **kw)
    m = meshlib.make_lane_mesh(lane=4)
    sharded = training.train_lanes(_uneven_lanes(), loss, mesh=m, **kw)
    _assert_lane_results_match(base, sharded)


@pytest.mark.needs_devices(4)
@pytest.mark.parametrize("rows", [128, 130])
def test_train_lanes_row_sharded_parity(rows):
    """lane=2 x data=2 with shard_rows: 128 rows divide the data axis,
    130 don't (policy._divisible silently drops row sharding) — parity
    must hold either way."""
    rng = np.random.RandomState(1)
    lanes = [LaneSpec(ae.init_autoencoder(jax.random.PRNGKey(20 + i),
                                          [6, 8, 4]),
                      {"x": jnp.asarray(
                          rng.randn(rows, 6).astype(np.float32))},
                      seed=i)
             for i in range(2)]
    kw = dict(batch_size=16, max_epochs=4, patience=3, lr=1e-3)
    base = training.train_lanes(lanes, ae.masked_recon_loss, **kw)
    m = meshlib.make_lane_mesh(lane=2, data=2)
    sharded = training.train_lanes(lanes, ae.masked_recon_loss, mesh=m,
                                   shard_rows=True, **kw)
    _assert_lane_results_match(base, sharded)


@pytest.mark.needs_devices(4)
def test_run_apcvfl_replicated_mesh_parity():
    """The whole protocol through a lane mesh: engine-level outputs exact,
    probe metrics within the replica CV band (test_replicas discipline)."""
    seeds = [0, 1]
    scs = [build_scenario(ScenarioSpec(dataset="bcw", n_aligned=120,
                                       n_active_features=5, seed=s))
           for s in seeds]
    kw = dict(max_epochs=3)
    base = pipeline.run_apcvfl_replicated(scs, seeds=seeds, **kw)
    m = meshlib.make_lane_mesh(lane=4)
    meshed = pipeline.run_apcvfl_replicated(scs, seeds=seeds, mesh=m, **kw)
    for a, b in zip(base, meshed):
        assert a.epochs == b.epochs
        assert a.comm == b.comm
        assert a.rounds == b.rounds and a.z_dim == b.z_dim
        assert _max_leaf_diff(a.params["g3"], b.params["g3"]) < 1e-4
        for k in a.metrics:
            assert abs(a.metrics[k] - b.metrics[k]) < METRIC_TOL, (k,)


# ---------------------------------------------------------------------------
# ExperimentSpec.devices dispatch
# ---------------------------------------------------------------------------

def test_spec_devices_json_roundtrip():
    spec = ExperimentSpec(name="m", methods=(MethodSpec("apcvfl"),),
                          devices={"lane": 2, "data": 2})
    back = ExperimentSpec.from_json(spec.to_json())
    assert back == spec and back.devices == {"lane": 2, "data": 2}


def test_spec_devices_unknown_axis_rejected():
    spec = ExperimentSpec(name="bad", methods=(MethodSpec("local"),),
                          devices={"model": 2})
    with pytest.raises(ValueError, match="unknown mesh axes"):
        sweep(spec)


def test_spec_devices_non_positive_rejected():
    spec = ExperimentSpec(name="bad", methods=(MethodSpec("local"),),
                          devices={"lane": 0})
    with pytest.raises(ValueError, match="positive int"):
        sweep(spec)


def test_spec_devices_too_many_raises_before_any_run():
    """The mesh is built (and validated) before any scenario or model —
    a device shortfall fails fast with the XLA_FLAGS recipe."""
    spec = ExperimentSpec(
        name="big", methods=(MethodSpec("apcvfl"),),
        devices={"lane": jax.device_count() * 2})
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        sweep(spec)


def test_sweep_threads_mesh_into_replicated_runner(monkeypatch):
    """devices={} keeps legacy runner signatures working; a non-empty
    devices dict delivers the built mesh as mesh= to the runner."""
    import repro.experiments.registry as reg
    from repro.experiments.registry import get_method
    from repro.experiments.results import RunResult

    get_method("apcvfl")                       # force adapter registration
    seen = {}

    def spy(scenarios, mspec, *, seeds, mesh=None):
        seen["mesh"] = mesh
        return [RunResult(method="apcvfl", metrics={}, rounds=0,
                          seed=s) for s in seeds]

    entry = reg._REGISTRY["apcvfl"]
    monkeypatch.setitem(reg._REGISTRY, "apcvfl",
                        dataclasses.replace(entry, replicated_fn=spy))
    spec = ExperimentSpec(name="spy", dataset="bcw", aligned=(100,),
                          seeds=(0, 1), methods=(MethodSpec("apcvfl"),),
                          devices={"lane": 1})
    sweep(spec)
    assert seen["mesh"] is not None
    assert seen["mesh"].axis_names == ("lane", "data")

    def legacy(scenarios, mspec, seeds):       # no mesh kwarg at all
        seen["legacy"] = True
        return [RunResult(method="apcvfl", metrics={}, rounds=0,
                          seed=s) for s in seeds]

    monkeypatch.setitem(reg._REGISTRY, "apcvfl",
                        dataclasses.replace(entry, replicated_fn=legacy))
    sweep(dataclasses.replace(spec, devices={}))
    assert seen.get("legacy")


# ---------------------------------------------------------------------------
# streaming scale generator
# ---------------------------------------------------------------------------

def test_scale_party_shape_dtype_residency():
    x = scale.make_scale_party(1000, n_features=6, n_latent=4, seed=3)
    assert isinstance(x, jax.Array)
    assert x.shape == (1000, 6) and x.dtype == jnp.float32
    # approximately standardized by construction
    assert abs(float(x.mean())) < 0.1
    assert 0.7 < float(x.std()) < 1.3


def test_scale_party_deterministic_and_blocked():
    a = scale.make_scale_party(700, n_features=5, block_rows=256, seed=1)
    b = scale.make_scale_party(700, n_features=5, block_rows=256, seed=1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = scale.make_scale_party(700, n_features=5, block_rows=256, seed=2)
    assert float(jnp.max(jnp.abs(a - c))) > 0.1


def test_scale_parties_share_latents():
    """Vertical partition semantics: with zero feature noise, party p's
    feature j and party p+1's feature j-1 read the same latent mix —
    identical columns prove all parties draw one shared z per row."""
    kw = dict(n_features=4, n_latent=4, noise=0.0, seed=5)
    p0 = scale.make_scale_party(300, party=0, **kw)
    p1 = scale.make_scale_party(300, party=1, **kw)
    np.testing.assert_allclose(np.asarray(p0[:, 1]), np.asarray(p1[:, 0]),
                               rtol=1e-6)
    assert float(jnp.max(jnp.abs(p0 - p1))) > 0.1   # views still differ


def test_scale_lanes_shapes_and_training():
    lanes = scale.make_scale_lanes(512, 2, n_features=6,
                                   widths=[6, 8, 4], seeds=(0, 1))
    assert len(lanes) == 4                     # parties x seeds
    assert all(lane.data["x"].shape == (512, 6) for lane in lanes)
    assert len({lane.seed for lane in lanes}) == 4
    rs = training.train_lanes(lanes, ae.masked_recon_loss, batch_size=128,
                              max_epochs=2, patience=2)
    assert len(rs) == 4
    for r in rs:
        assert r.epochs_run >= 1
        assert np.isfinite(r.train_loss).all()


def test_scale_lanes_width_mismatch_rejected():
    with pytest.raises(ValueError, match="must equal n_features"):
        scale.make_scale_lanes(64, 2, n_features=6, widths=[5, 8, 4])
