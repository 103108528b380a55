"""Unstacking the lane engine's best-val parameters
(``training._unstack_lane_params``): one compiled program a shape group
gives every live lane the leaves an eager ``stack[i]`` gives, bit for bit
and placed alike, on one device and on a lane mesh with dead lanes."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import autoencoder as ae
from repro.core import padding, training
from repro.core.training import LaneSpec

KW = dict(batch_size=16, max_epochs=3, patience=2, lr=1e-3)


def _fleet():
    """Two shape groups of three lanes each, interleaved: an active-like
    ``[4, 8, 3]`` lane and a passive-like ``[7, 12, 3]`` one per seed."""
    rng = np.random.RandomState(0)
    lanes = []
    for s in range(3):
        for n, w in ((80, [4, 8, 3]), (96, [7, 12, 3])):
            x = rng.randn(n, w[0]).astype(np.float32)
            lanes.append(LaneSpec(ae.init_autoencoder(
                jax.random.PRNGKey(10 * s + w[0]), w), {"x": x}, seed=s))
    return lanes


def _bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return (jax.tree.structure(a) == jax.tree.structure(b)
            and len(la) == len(lb)
            and all(x.dtype == y.dtype and x.shape == y.shape
                    and np.asarray(x).tobytes() == np.asarray(y).tobytes()
                    for x, y in zip(la, lb)))


def test_groups_share_every_leaf_shape():
    """Inside a ``_lane_groups`` group nothing is padded, so the strip to
    each lane's own shapes that the unstack once did was the identity."""
    specs = _fleet()
    groups = training._lane_groups(specs)
    assert sorted(groups) == [[0, 2, 4], [1, 3, 5]]
    for idxs in groups:
        shapes = {tuple(np.shape(l)
                        for l in jax.tree.leaves(specs[i].params))
                  for i in idxs}
        assert len(shapes) == 1
        stack = padding.pad_stack([specs[i].params for i in idxs])
        assert [l.shape[1:] for l in jax.tree.leaves(stack)] \
            == list(shapes.pop())


def test_compiled_unstack_equals_eager_indexing():
    specs = _fleet()
    for idxs in training._lane_groups(specs):
        gspecs = [specs[i] for i in idxs]
        stack = jax.tree.map(lambda l: l * 1.5,
                             padding.pad_stack([sp.params for sp in gspecs]))
        got = training._unstack_lane_params(gspecs, stack)
        assert len(got) == len(idxs)
        for i, tree in enumerate(got):
            assert _bitwise_equal(tree, jax.tree.map(lambda l: l[i], stack))
            for x, y in zip(jax.tree.leaves(tree),
                            jax.tree.leaves(stack)):
                assert x.sharding == y[i].sharding


def test_train_lanes_returns_what_eager_unstacking_returns(monkeypatch):
    """Whole fits: the compiled unstack and the eager ``l[i]`` it replaced
    give the same parameters, lane by lane, in both paths."""
    specs = _fleet()
    fused = training.train_lanes(specs, ae.recon_loss, **KW)
    epochwise = training.train_lanes_epochwise(specs, ae.recon_loss, **KW)
    monkeypatch.setattr(training, "_unstack_lanes", lambda leaves, k: [
        [l[i] for l in leaves] for i in range(k)])
    for got, want in ((fused, training.train_lanes(specs, ae.recon_loss,
                                                   **KW)),
                      (epochwise, training.train_lanes_epochwise(
                          specs, ae.recon_loss, **KW))):
        assert len(got) == len(specs)
        for g, w, sp in zip(got, want, specs):
            assert _bitwise_equal(g.params, w.params)
            assert (jax.tree.structure(g.params)
                    == jax.tree.structure(sp.params))


def test_second_fit_adds_no_trace():
    if not hasattr(training._unstack_lanes, "_cache_size"):
        pytest.skip("this jax version has no PjitFunction._cache_size")
    specs = _fleet()
    training.train_lanes(specs, ae.recon_loss, **KW)
    traced = training._unstack_lanes._cache_size()
    assert traced >= 1
    training.train_lanes(_fleet(), ae.recon_loss, **KW)
    assert training._unstack_lanes._cache_size() == traced


# Six lanes on a four-chip lane mesh: the stack is padded to eight lanes,
# two of them dead.  Run in a child process, since the device count is
# fixed when JAX starts.
MESH_CHILD = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import autoencoder as ae
    from repro.core import padding, training
    from repro.core.training import LaneSpec
    from repro.launch.mesh import make_lane_mesh

    rng = np.random.RandomState(3)
    specs = [LaneSpec(ae.init_autoencoder(jax.random.PRNGKey(i), [5, 8, 3]),
                      {"x": rng.randn(64, 5).astype(np.float32)}, seed=i)
             for i in range(6)]
    mesh = make_lane_mesh(lane=4)
    kw = dict(batch_size=16, max_epochs=3, patience=2, lr=1e-3)

    def same(a, b):
        return all(np.asarray(x).tobytes() == np.asarray(y).tobytes()
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    # the unstack alone: a stack padded to 8 lanes and sharded on the mesh
    stack = jax.tree.map(lambda l: l * 1.5,
                         padding.pad_stack([sp.params for sp in specs]))
    live = jnp.ones((6,), bool)
    sharded = training._shard_lanes(
        mesh, stack, stack, jnp.zeros((6, 2), jnp.uint32),
        {"x": jnp.zeros((6, 4, 5))}, {"x": jnp.zeros((6, 4, 5))},
        live, live, live, shard_rows=False)[0]
    got = training._unstack_lane_params(specs, sharded)
    base = training._unstack_lane_params(specs, stack)
    eager = [jax.tree.map(lambda l: l[i], sharded) for i in range(6)]
    out = {
        "stack_lanes": [int(l.shape[0]) for l in jax.tree.leaves(sharded)],
        "lanes": len(got),
        "equal": all(same(g, b) for g, b in zip(got, base)),
        "eager_equal": all(same(g, e) for g, e in zip(got, eager)),
        "placed": all(x.sharding == y.sharding
                      for g, e in zip(got, eager)
                      for x, y in zip(jax.tree.leaves(g),
                                      jax.tree.leaves(e))),
        "replicated": all(x.sharding.spec == jax.sharding.PartitionSpec()
                          and x.sharding.mesh == mesh
                          for g in got for x in jax.tree.leaves(g)),
    }
    # whole fits, sharded against unsharded
    fit_mesh = training.train_lanes(specs, ae.recon_loss, mesh=mesh, **kw)
    fit_one = training.train_lanes(specs, ae.recon_loss, **kw)
    out["fit_lanes"] = len(fit_mesh)
    out["fit_equal"] = all(same(a.params, b.params)
                           for a, b in zip(fit_mesh, fit_one))
    out["fit_replicated"] = all(
        x.sharding.spec == jax.sharding.PartitionSpec()
        for r in fit_mesh for x in jax.tree.leaves(r.params))
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def on_mesh():
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    proc = subprocess.run([sys.executable, "-c", MESH_CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mesh_unstack_leaves_out_dead_lanes(on_mesh):
    assert set(on_mesh["stack_lanes"]) == {8}
    assert on_mesh["lanes"] == 6
    assert on_mesh["fit_lanes"] == 6


def test_mesh_unstack_equals_unsharded(on_mesh):
    assert on_mesh["equal"]
    assert on_mesh["eager_equal"]
    assert on_mesh["fit_equal"]


def test_mesh_unstack_placed_as_eager_indexing(on_mesh):
    assert on_mesh["placed"]
    assert on_mesh["replicated"]
    assert on_mesh["fit_replicated"]
