"""End-to-end behaviour tests for the paper's system."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm, pipeline
from repro.data.synthetic import make_dataset
from repro.data.vertical import make_scenario

ENV = dict(os.environ, PYTHONPATH="src")


@pytest.fixture(scope="module")
def scenario():
    ds = make_dataset("bcw", seed=3)
    return make_scenario(ds, n_active_features=3, n_aligned=200, seed=3)


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    from repro.launch.compile_cache import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_config):
    from repro.launch.compile_cache import use_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_single_communication_round(scenario):
    """Headline claim: APC-VFL needs exactly ONE data exchange, and its
    size follows Eq. 6 exactly."""
    r = pipeline.run_apcvfl(scenario, max_epochs=8)
    assert r.rounds == 1
    data = [t for t in r.channel.log if t.stage != "psi"]
    assert len(data) == 1
    assert data[0].nbytes == comm.apcvfl_footprint_bytes(scenario.n_aligned)
    assert data[0].direction == "uplink"       # passive -> active


def test_active_party_inference_is_independent(scenario):
    """After training, inference uses ONLY g3 + classifier on active data —
    no passive-party state is referenced."""
    from repro.core import autoencoder as ae
    r = pipeline.run_apcvfl(scenario, max_epochs=8)
    g3 = r.params["g3"]
    z = ae.encode(g3, jnp.asarray(scenario.active.x[:10]))
    assert z.shape == (10, r.z_dim)
    assert np.isfinite(np.asarray(z)).all()


def test_unaligned_samples_used_in_training(scenario):
    """The student autoencoder trains on the FULL active dataset (aligned +
    unaligned) — the capability missing from SplitNN/FedCVT."""
    n_total = len(scenario.active.x)
    assert n_total > scenario.n_aligned   # scenario really has unaligned rows
    r = pipeline.run_apcvfl(scenario, max_epochs=8)
    assert 0.0 <= r.metrics["accuracy"] <= 1.0


def test_encoder_quality_probe_algorithm1(scenario):
    """Appendix F Algorithm 1 runs and reports the equivalence gap."""
    out = pipeline.train_encoder_with_probe(
        scenario.active.x, scenario.active.y, scenario.n_classes,
        [scenario.active.x.shape[1], 32, 64], max_epochs=3, k=3)
    assert len(out["history"]["probe"]) == 3
    assert np.isfinite(out["gap"])


def test_lm_training_loop_improves():
    """The distributed-runtime training path optimizes a real objective."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "internlm2-1.8b", "--smoke", "--steps", "30", "--batch", "4",
         "--seq", "64"], capture_output=True, text=True, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert '"improved": true' in out.stdout


def test_checkpoint_roundtrip():
    from repro.checkpoint import ckpt
    from repro.configs import get_smoke
    from repro.models import model as M
    from repro.sharding.policy import init_params
    cfg = get_smoke("internlm2-1.8b")
    params = init_params(M.schema(cfg), jax.random.PRNGKey(0), jnp.float32)
    ckpt.save("/tmp/test_ckpt.npz", {"params": params}, step=7)
    back = ckpt.restore("/tmp/test_ckpt.npz", {"params": params})
    a = jax.tree.leaves(params)
    b = jax.tree.leaves(back["params"])
    assert all(np.allclose(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))


def test_dryrun_single_combo_subprocess():
    """One real multi-device lowering (512 fake devices) as a system test."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "internlm2-1.8b", "--shape", "decode_32k", "--out",
         "/tmp/test_dryrun"], capture_output=True, text=True, env=ENV)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "wrote" in out.stdout
