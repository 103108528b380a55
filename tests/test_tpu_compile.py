"""Compile the VFL Pallas kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib's TPU plug-in, and it compiles
for a topology that is described rather than attached.  Each test lowers
one kernel at the paper's mimic3 Table-3 widths through Mosaic and checks
that the compiled HLO holds the kernel as a ``tpu_custom_call``: this is
what interpret mode cannot show (block-shape and primitive lowering
rules).  Nothing runs, so these tests say nothing about values or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.distill_loss import fused_distill_rows
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.lane_mlp import fused_mlp2
from repro.kernels.probe import probe_grad_step

# mimic3 Table-3 encoder/decoder pairs (din, hidden, dout): g1 active and
# passive (5->64->128 / 128->64->5, 10->128->256), g2 joint (384->256->256)
# and g3 distilled (5->256->256)
MLP_PAIRS = [(5, 64, 128), (128, 64, 5), (10, 128, 256), (384, 256, 256),
             (5, 256, 256)]
ROWS = 128          # the paper's batch size
LANES = 2           # seeds (0, 1) as replica lanes


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU plug-in, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _assert_kernel_compiles(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _mlp_args(spec, din, h, dout, lanes=None):
    lead = () if lanes is None else (lanes,)
    shapes = [(ROWS, din), (din, h), (h,), (h, dout), (dout,)]
    return [spec(lead + s) for s in shapes]


def _mlp_fwd(*a):
    return fused_mlp2(*a)


def _mlp_grad(*a):
    return jax.grad(lambda *p: jnp.sum(jnp.square(fused_mlp2(*p))),
                    argnums=(0, 1, 2, 3, 4))(*a)


@pytest.mark.parametrize("din,h,dout", MLP_PAIRS)
@pytest.mark.parametrize("lanes", [None, LANES], ids=["solo", "lanes"])
@pytest.mark.parametrize("fn", [_mlp_fwd, _mlp_grad], ids=["fwd", "grad"])
def test_fused_mlp2_compiles(spec, fn, lanes, din, h, dout):
    f = fn if lanes is None else jax.vmap(fn)
    _assert_kernel_compiles(f, *_mlp_args(spec, din, h, dout, lanes))


def test_probe_grad_step_compiles_over_folds(spec):
    folds, n, d, c = 10, 500, 256, 4
    step = jax.vmap(lambda w, b, x, y, rw: probe_grad_step(w, b, x, y, rw),
                    in_axes=(0, 0, None, None, 0))
    _assert_kernel_compiles(step, spec((folds, d, c)), spec((folds, c)),
                            spec((n, d)), spec((n,), jnp.int32),
                            spec((folds, n)))


def _distill_args(spec, lanes=None):
    lead = () if lanes is None else (lanes,)
    D, M = 5, 256
    shapes = [(ROWS, D), (ROWS, D), (ROWS, M), (ROWS, M), (ROWS,)]
    return [spec(lead + s) for s in shapes]


def _distill_grad(*a):
    return jax.grad(lambda *p: jnp.sum(fused_distill_rows(*p)),
                    argnums=(0, 1, 2, 3, 4))(*a)


@pytest.mark.parametrize("lanes", [None, LANES], ids=["solo", "lanes"])
@pytest.mark.parametrize("fn", [lambda *a: fused_distill_rows(*a),
                                _distill_grad], ids=["fwd", "grad"])
def test_fused_distill_rows_compiles(spec, fn, lanes):
    f = fn if lanes is None else jax.vmap(fn)
    _assert_kernel_compiles(f, *_distill_args(spec, lanes))


@pytest.mark.parametrize("d,c,act", [(5, 256, "selu"), (256, 4, "none")])
def test_int8_matmul_compiles(spec, d, c, act):
    _assert_kernel_compiles(
        lambda x, w, s, b: int8_matmul(x, w, s, b, act=act),
        spec((ROWS, d)), spec((d, c), jnp.int8), spec((c,)), spec((c,)))
