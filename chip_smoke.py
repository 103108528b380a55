"""Run the APC-VFL train -> export -> serve path once on a TPU.

    python chip_smoke.py               # one chip: device, train, kernels, serve
    python chip_smoke.py --four-chips  # the lane-sharded scale fit, 4 chips vs 1

One process drives the chip through the entry points a user calls, at the
paper's full widths (mimic3, Table-3 encoders, batch 128); only
``max_epochs`` is cut.  Every phase checks its own output and any failed
check exits non-zero.  Without a TPU the script stops at the device phase
and prints no result.  The last line of a passing run is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.analysis import guards  # noqa: E402
from repro.core import autoencoder as ae  # noqa: E402
from repro.core import distill, training  # noqa: E402
from repro.core.psi import psi  # noqa: E402
from repro.experiments import ExperimentSpec, MethodSpec, sweep  # noqa: E402
from repro.experiments.sweeps import build_scenario  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.serve import quant  # noqa: E402
from repro.serve import runtime as rt  # noqa: E402
from repro.serve import vfl as sv  # noqa: E402

# The paper's protocol settings (configs/apcvfl_paper.py) on mimic3
# (20000 x 15, 4 classes) in the low-alignment regime of
# examples/specs/reduced_alignment_fig8.json; max_epochs is the one cut.
DATASET = "mimic3"
ALIGNED = 500
ACTIVE_FEATURES = 5
SEEDS = (0, 1)                        # two seeds: the replica-lane path
HPARAMS = {"batch_size": 128, "lr": 1e-3, "patience": 10}
EPOCHS = 3
KERNEL_FIT_EPOCHS = 1
REQUESTS = 400

# Kernel bounds.  Each kernel's output is compared with its oracle taken
# at jax.default_matmul_precision("highest"), leaf by leaf (every output
# and every gradient), as max|diff| / max|oracle| of that leaf.  A float32
# matmul on a TPU runs by default on bfloat16 operand passes, in XLA and in
# Mosaic alike, so each leaf is held to the accuracy of the path it
# replaces: the error of the same oracle compiled by XLA at the default
# precision, times XLA_ERR_FACTOR, plus FLOAT32_FLOOR.  On a v5e every
# matmul kernel's error equalled XLA's to four digits, hence the 10 %
# margin; the floor covers float32 rounding where XLA's error is nil (the
# kernels without a matmul).
XLA_ERR_FACTOR = 1.1
FLOAT32_FLOOR = 1e-5
KERNEL_LANES = 2                      # the vmapped lane-engine cases

# The lane-sharded scale fit (benchmarks/trainbench.py --scale grid).
SCALE = {"rows": 1_000_000, "parties": 8, "seeds": (0, 1), "features": 16,
         "batch_size": 8192, "epochs": 2}
# final train losses of the 4-chip and the 1-chip fit: each lane runs the
# same program on its own device, so only reduction order may differ
SCALE_LOSS_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    """A phase produced output that fails its check."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(min_count: int = 1) -> dict:
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's platform is "
                         f"{d0.platform!r}")
    if len(devices) < min_count:
        raise SystemExit(f"chip_smoke: needs {min_count} TPU devices, "
                         f"found {len(devices)}")
    log(f"device: {d0.device_kind}, count {len(devices)}, "
        f"jax {jax.__version__}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def protocol_spec(epochs: int, use_kernel: bool = False) -> ExperimentSpec:
    params = {"use_kernel": True} if use_kernel else {}
    return ExperimentSpec(
        name="chip-smoke", dataset=DATASET, aligned=(ALIGNED,),
        n_active_features=ACTIVE_FEATURES, seeds=SEEDS,
        methods=(MethodSpec("apcvfl", params=params),),
        overrides={**HPARAMS, "max_epochs": epochs})


def stage_losses(result, sc) -> dict:
    """Each stage's objective at the trained parameters the active party
    holds, on that stage's own inputs.  The passive encoder stays with the
    passive party; its output is the exchanged latent block."""
    _, idx_a, _ = psi(sc.active.ids, sc.passive.ids)
    p = result.params
    xa = jnp.asarray(sc.active.x)
    zp = result.artifacts["z_passive_aligned"]
    zj = jnp.concatenate([ae.encode(p["g1_active"], xa[idx_a]), zp], axis=1)
    z_t = ae.encode(p["g2"], zj)
    z_teacher = jnp.zeros((len(xa), z_t.shape[1]), jnp.float32)
    z_teacher = z_teacher.at[idx_a].set(z_t)
    aligned = jnp.zeros((len(xa),), jnp.float32).at[idx_a].set(1.0)
    losses = {
        "g1_active": ae.recon_loss(p["g1_active"], {"x": xa}),
        "exchange": jnp.mean(jnp.square(zp)),
        "g2": ae.recon_loss(p["g2"], {"x": zj}),
        "g3": distill.distill_loss(p["g3"], {"x": xa, "z_teacher": z_teacher,
                                             "aligned": aligned}),
    }
    return {k: float(v) for k, v in jax.device_get(losses).items()}


def check_protocol(results, scenarios, label: str) -> None:
    z_p = ae.table3_encoder("g1_passive", 1)[-1]
    for r, sc in zip(results, scenarios):
        losses = stage_losses(r, sc)
        acc = r.metrics["accuracy"]
        sent = r.comm["by_stage"]["step1"]     # the one latent exchange
        log(f"{label} seed {r.seed}: rounds {r.rounds}, accuracy {acc:.4f}, "
            f"exchange {sent} bytes, epochs {r.epochs}, losses "
            + " ".join(f"{k}={v:.5f}" for k, v in losses.items()))
        check(r.rounds == 1, f"{label}: rounds {r.rounds} != 1")
        check(all(np.isfinite(v) for v in losses.values()),
              f"{label}: non-finite stage loss {losses}")
        check(0.0 <= acc <= 1.0, f"{label}: accuracy {acc} outside [0, 1]")
        check(sent == ALIGNED * z_p * 4,
              f"{label}: exchange {sent} bytes != {ALIGNED} x {z_p} x 4")


def g1_lanes(scenarios, seed_offset: int):
    """The replica-lane g1 stage exactly as the protocol builds it: an
    active and a passive Table-3 autoencoder per scenario."""
    lanes = []
    for sc, s in zip(scenarios, SEEDS):
        s = s + seed_offset
        k1, k2 = jax.random.split(jax.random.PRNGKey(s))
        lanes.append(training.LaneSpec(ae.init_autoencoder(
            k1, ae.table3_encoder("g1_active", sc.active.x.shape[1])),
            {"x": sc.active.x}, s))
        lanes.append(training.LaneSpec(ae.init_autoencoder(
            k2, ae.table3_encoder("g1_passive", sc.passive.x.shape[1])),
            {"x": sc.passive.x}, s + 1))
    return lanes


def train_phase(epochs: int = EPOCHS):
    spec = protocol_spec(epochs)
    t0 = time.perf_counter()
    results = sweep(spec)
    log(f"train: {len(results)} protocol runs in "
        f"{time.perf_counter() - t0:.1f} s wall (compiles included)")
    scenarios = [build_scenario(s) for s in spec.scenarios()]
    check_protocol(results, scenarios, "train")

    # one more warmed fit of one stage: no compile, one device->host sync
    lanes = g1_lanes(scenarios, seed_offset=100)
    kw = dict(HPARAMS, max_epochs=epochs)
    with guards.compile_counter(budget=0, label="warm g1 fit"), \
            guards.no_host_sync(allowed=1, label="warm g1 fit") as syncs:
        fits = training.train_lanes(lanes, ae.make_masked_recon_loss(False),
                                    **kw)
    check(syncs.device_gets == 1,
          f"warm g1 fit: {syncs.device_gets} host syncs != 1")
    check(all(np.isfinite(f.train_loss[-1]) for f in fits),
          "warm g1 fit: non-finite loss")
    log(f"train: warm g1 fit of {len(lanes)} lanes at 0 compiles, "
        f"{syncs.device_gets} host sync")
    return results, scenarios


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _leaf_errs(got, want) -> list:
    """max|diff| / max|oracle| of each leaf, against that leaf's own
    oracle, so a small leaf (a bias gradient) is not judged by a large
    one."""
    return [float(jnp.max(jnp.abs(g - w)))
            / max(float(jnp.max(jnp.abs(w))), 1e-30)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]


def _kernel_case(name, fn, oracle, args) -> str:
    """Compile ``fn`` (which must hold a Mosaic kernel), compare it with
    ``oracle`` leaf by leaf; returns a failure message, or '' when the
    case passes."""
    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        return f"kernel {name}: no tpu_custom_call in its HLO"
    got = compiled(*args)
    xla = jax.jit(oracle)(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(oracle)(*args)
    errs, errs_xla = _leaf_errs(got, want), _leaf_errs(xla, want)
    bounds = [XLA_ERR_FACTOR * e + FLOAT32_FLOOR for e in errs_xla]
    fmt = lambda v: "[" + ", ".join(f"{x:.3e}" for x in v) + "]"
    log(f"kernel {name}: max|diff|/max|oracle| per leaf {fmt(errs)} (XLA at "
        f"default precision {fmt(errs_xla)})")
    bad = [i for i, (e, b) in enumerate(zip(errs, bounds)) if e > b]
    return "" if not bad else (
        f"kernel {name}: leaves {bad} over their bounds, "
        f"{fmt([errs[i] for i in bad])} > {fmt([bounds[i] for i in bad])}")


def _mlp_pairs(scenario) -> list:
    """(din, hidden, dout) of every Table-3 encoder and decoder the
    protocol trains on this scenario."""
    da, dp = scenario.active.x.shape[1], scenario.passive.x.shape[1]
    za = ae.table3_encoder("g1_active", da)[-1]
    zp = ae.table3_encoder("g1_passive", dp)[-1]
    encoders = [ae.table3_encoder("g1_active", da),
                ae.table3_encoder("g1_passive", dp),
                ae.table3_encoder("g2", za + zp),
                ae.table3_encoder("g3", da)]
    return [tuple(w) for w in encoders] + [tuple(w[::-1]) for w in encoders]


def kernel_phase(scenario, *, rows: int = 128, folds: int = 10,
                 lanes: int = KERNEL_LANES) -> None:
    """Every kernel the protocol and the int8 serving path use, at the
    train phase's widths; all cases run before a failure is raised."""
    failures = []
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 128))
    normal = lambda shape, s=1.0: s * jax.random.normal(next(keys), shape)

    def fwd_and_grad(name, fn, oracle, make_args, scalar):
        """Value and gradient of a kernel, alone and vmapped over ``lanes``
        lanes as the lane engine runs it."""
        for lead in ((), (lanes,)):
            f, o = (fn, oracle) if not lead else (jax.vmap(fn),
                                                  jax.vmap(oracle))
            label = name if not lead else f"{name} vmap {lanes} lanes"
            args = make_args(lead)
            argnums = tuple(range(len(args)))
            failures.append(_kernel_case(label, f, o, args))
            failures.append(_kernel_case(
                label + " grad",
                jax.grad(lambda *a, f=f: scalar(f(*a)), argnums=argnums),
                jax.grad(lambda *a, o=o: scalar(o(*a)), argnums=argnums),
                args))

    sumsq = lambda y: jnp.sum(jnp.square(y))
    for din, h, dout in _mlp_pairs(scenario):
        fwd_and_grad(
            f"fused_mlp2 {din}-{h}-{dout}", ops.fused_mlp2, ref.mlp2_ref,
            lambda lead, din=din, h=h, dout=dout: (
                normal(lead + (rows, din)),
                normal(lead + (din, h), din ** -0.5),
                normal(lead + (h,), 0.1),
                normal(lead + (h, dout), h ** -0.5),
                normal(lead + (dout,), 0.1)),
            sumsq)

    D = scenario.active.x.shape[1]
    M = ae.table3_encoder("g3", D)[-1]
    fwd_and_grad(
        f"fused_distill_rows D={D} M={M}",
        lambda *a: jnp.mean(ops.fused_distill_rows(*a)),
        ref.fused_distill_loss_ref,
        lambda lead: (
            normal(lead + (rows, D)), normal(lead + (rows, D)),
            normal(lead + (rows, M)), normal(lead + (rows, M)),
            (jax.random.uniform(next(keys), lead + (rows,)) < 0.3).astype(
                jnp.float32)),
        jnp.sum)

    n, c = len(scenario.active.x), scenario.n_classes
    x = normal((n, M))
    y = jax.random.randint(next(keys), (n,), 0, c)
    rws = (jax.random.uniform(next(keys), (folds, n)) > 0.1).astype(
        jnp.float32)
    fold_step = lambda step: jax.vmap(
        lambda w, b, rw: step(w, b, x, y, rw))
    failures.append(_kernel_case(
        f"probe_grad_step {folds} folds n={n} d={M} c={c}",
        fold_step(ops.probe_grad_step), fold_step(ref.probe_grad_ref),
        (normal((folds, M, c), 0.1), normal((folds, c), 0.1), rws)))

    selu_ref = lambda *a: jax.nn.selu(ref.int8_matmul_ref(*a))
    for d, cout, act, oracle in ((D, M, "selu", selu_ref),
                                 (M, c, "none", ref.int8_matmul_ref)):
        w_q, scale = quant.quantize_weight(np.asarray(normal((d, cout))))
        iargs = (normal((rows, d)), jnp.asarray(w_q), jnp.asarray(scale),
                 normal((cout,), 0.1))
        failures.append(_kernel_case(
            f"int8_matmul {d}->{cout} {act}",
            lambda *a, act=act: ops.int8_matmul(*a, act=act), oracle, iargs))
    failures = [f for f in failures if f]
    check(not failures, "; ".join(failures))


def kernel_fit_phase(epochs: int = KERNEL_FIT_EPOCHS) -> None:
    spec = protocol_spec(epochs, use_kernel=True)
    results = sweep(spec)
    scenarios = [build_scenario(s) for s in spec.scenarios()]
    check_protocol(results, scenarios, "kernel fit")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(result, scenario, *, requests: int = REQUESTS) -> None:
    bundle = sv.export_bundle(result, scenario)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "bundle")
        bundle.save(path)
        loaded = sv.ModelBundle.load(path)
    registry = rt.TenantRegistry()
    engine = registry.register("t0", loaded)
    registry.warmup()
    stream = rt.make_timed_stream(scenario.active.x, scenario.active.ids,
                                  requests, tenant="t0", arrivals="poisson",
                                  seed=1, p_known=0.5)
    runtime = rt.ServingRuntime(registry)
    with guards.compile_counter(budget=0, label="served stream"):
        report = runtime.run(stream)
    parity = rt.verify_dispatch_parity(runtime, {"t0": loaded})["t0"]
    hits = engine.cache.hits
    log(f"serve: {report['served']} served + {report['shed_requests']} shed "
        f"of {report['requests']} requests, {report['dispatches']} "
        f"dispatches, {hits} cache hits, 0 compiles after warmup, parity "
        f"{parity}")
    check(report["served"] + report["shed_requests"] == requests,
          f"serve: served + shed != {requests} requests")
    check(hits > 0, "serve: no cache hit, the collaborative path never ran")
    check(parity["bit_identical"], f"serve: dispatch parity failed {parity}")


# ---------------------------------------------------------------------------
# four chips: the lane-sharded scale fit
# ---------------------------------------------------------------------------

def _bytes(key: str) -> list:
    return [d.memory_stats()[key] for d in jax.devices()]


def scale_fit(mesh, label: str):
    """The scale grid's lanes trained on ``mesh`` (one chip when None).
    Prints each device's bytes in use before and after the fit, and its
    peak; returns each lane's final train loss, the lanes' feature bytes
    and each device's rise from bytes in use before the fit to its peak."""
    from repro.data.scale import make_scale_lanes
    lanes = make_scale_lanes(SCALE["rows"], SCALE["parties"],
                             n_features=SCALE["features"],
                             seeds=SCALE["seeds"], mesh=mesh)
    lane_bytes = sum(sp.data["x"].nbytes for sp in lanes)
    before = _bytes("bytes_in_use")
    fits = training.train_lanes(
        lanes, ae.masked_recon_loss, batch_size=SCALE["batch_size"],
        max_epochs=SCALE["epochs"], patience=SCALE["epochs"], mesh=mesh)
    after, peak = _bytes("bytes_in_use"), _bytes("peak_bytes_in_use")
    log(f"{label}: per device, bytes_in_use before the fit {before}, after "
        f"it {after}; peak_bytes_in_use {peak}")
    rise = [p - b for p, b in zip(peak, before)]
    return np.asarray([f.train_loss[-1] for f in fits]), lane_bytes, rise


def four_chip_phase() -> None:
    from repro.launch.mesh import make_lane_mesh
    # the sharded fit runs first: peak_bytes_in_use only ever grows, so
    # devices 1-3 can reach a peak only through it
    loss4, lane_bytes, rise4 = scale_fit(make_lane_mesh(lane=4),
                                         "four chips, lane-sharded fit")
    loss1, _, _ = scale_fit(None, "one chip, unsharded fit")
    rel = float(np.max(np.abs(loss4 - loss1) / np.abs(loss1)))
    log(f"scale fit: {len(loss4)} lanes, final train loss 4 chips "
        f"{loss4.tolist()} vs 1 chip {loss1.tolist()}, max rel diff "
        f"{rel:.3e} (bound {SCALE_LOSS_RTOL:.0e})")
    check(rel <= SCALE_LOSS_RTOL,
          f"scale fit: 4-chip losses differ by {rel:.3e}")
    # each device's quarter of the lanes' rows, at least half of it held
    # at once during the sharded fit: zero on devices 1-3 would mean the
    # lanes never left device 0
    share = lane_bytes / 4
    log(f"four chips: peak rise per device {rise4} bytes, each device's "
        f"share of the lanes' {lane_bytes} feature bytes {share:.0f}")
    check(all(r >= share / 2 for r in rise4[:4]),
          f"scale fit: a device held under half its share of the lanes "
          f"{rise4}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded scale fit on 4 chips "
                         "and its 1-chip comparison")
    args = ap.parse_args(argv)
    use_compile_cache()
    device = device_phase(min_count=4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase()
    else:
        results, scenarios = train_phase()
        kernel_phase(scenarios[0])
        kernel_fit_phase()
        serve_phase(results[0], scenarios[0])
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
