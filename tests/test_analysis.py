"""jaxlint subsystem tests.

Static layer: every rule R001-R007 fires on its bad fixture and stays
silent on the matching good one (the good fixtures encode the repo's
sanctioned idioms: kw-only statics, shape-derived branching, pad-to-
multiple grids, rebind-after-donate).  Baseline suppression round-trips,
and the real tree lints clean against the committed baseline.

Runtime layer: the engine contracts from ANALYSIS_budgets.json are
asserted for real — one accounted host sync per ``train`` fit and per
``train_lanes`` fit at zero warm compiles, zero compiles on a warmed
serve bucket, implicit device->host conversions trapped at the call
site, and engine pytrees all in the float32/int32 family.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import guards
from repro.analysis.lint import (apply_baseline, lint_paths, lint_source,
                                 load_baseline, write_baseline)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
BASELINE = os.path.join(REPO, "src", "repro", "analysis", "baseline.json")


def rules_of(findings):
    return sorted({f.rule for f in findings})


def lint_snippet(code):
    return lint_source(textwrap.dedent(code))


# ---------------------------------------------------------------------------
# static rules: bad fixture fires, good fixture is silent
# ---------------------------------------------------------------------------

def test_r001_host_call_fires_on_np_in_jitted_body():
    bad = lint_snippet("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.log(x) + 1.0
    """)
    assert "R001" in rules_of(bad)


def test_r001_silent_on_static_hyperparam_cast():
    good = lint_snippet("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, *, scale: float = 2.0):
            return jnp.log(x) * float(scale)
    """)
    assert "R001" not in rules_of(good)


def test_r001_fires_on_item_sync():
    bad = lint_snippet("""
        import jax

        @jax.jit
        def f(x):
            return x.sum().item()
    """)
    assert "R001" in rules_of(bad)


def test_r002_fires_on_traced_branch():
    bad = lint_snippet("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    assert "R002" in rules_of(bad)


def test_r002_silent_on_static_and_shape_branches():
    good = lint_snippet("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, *, mode: str = "a"):
            if mode == "a":
                return x
            platform = jax.default_backend()
            if platform == "cpu" or jax.device_count() > 1:
                x = x * 2.0
            if x.ndim == 1:
                return -x
            n = len([k for k in x.shape])
            for i in range(n):
                if i < n - 1:
                    x = x + 1.0
            return jnp.where(x > 0, x, -x)
    """)
    assert "R002" not in rules_of(good)


def test_r002_propagates_tracedness_through_scan_body():
    bad = lint_snippet("""
        import jax

        def body(carry, x):
            if x > 0:
                carry = carry + x
            return carry, x

        def run(xs):
            return jax.lax.scan(body, 0.0, xs)
    """)
    assert "R002" in rules_of(bad)


def test_r003_fires_on_dict_literal_to_jit():
    bad = lint_snippet("""
        import jax

        @jax.jit
        def f(x, opts):
            return x * opts["s"]

        def call(x):
            return f(x, {"s": 2})
    """)
    assert "R003" in rules_of(bad)


def test_r003_silent_when_param_is_static():
    good = lint_snippet("""
        import jax
        from functools import partial

        @partial(jax.jit, static_argnames=("opts",))
        def f(x, opts):
            return x * 2.0

        def call(x):
            return f(x, {"s": 2})
    """)
    assert "R003" not in rules_of(good)


def test_r004_fires_on_use_after_donate():
    bad = lint_snippet("""
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, x):
            return state + x

        def run(state, xs):
            out = step(state, xs)
            return state + out
    """)
    assert "R004" in rules_of(bad)


def test_r004_silent_on_rebind_idiom_and_loop():
    good = lint_snippet("""
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, x):
            return state + x

        def run(state, xs):
            for x in xs:
                state = step(state, x)
            return state
    """)
    assert "R004" not in rules_of(good)


def test_r004_fires_on_loop_carried_donation():
    bad = lint_snippet("""
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, x):
            return state + x

        def run(state, xs):
            for x in xs:
                out = step(state, x)
            return out
    """)
    assert "R004" in rules_of(bad)


def test_r005_fires_on_key_reuse():
    bad = lint_snippet("""
        import jax

        def init(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.normal(key, (3,))
            return a + b
    """)
    assert "R005" in rules_of(bad)


def test_r005_silent_on_split_fold_in_and_exclusive_branches():
    good = lint_snippet("""
        import jax

        def init(key, kind: str):
            if kind == "a":
                return jax.random.normal(key, (3,))
            return jax.random.uniform(key, (3,))

        def epochs(base_key, n: int):
            outs = []
            for e in range(n):
                k = jax.random.fold_in(base_key, e)
                outs.append(jax.random.normal(k, (3,)))
            return outs

        def pair(key):
            k1, k2 = jax.random.split(key)
            return jax.random.normal(k1, (3,)) + jax.random.normal(k2, (3,))
    """)
    assert "R005" not in rules_of(good)


def test_r006_fires_on_unguarded_grid_floordiv():
    bad = lint_snippet("""
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(x, block: int):
            return pl.pallas_call(
                kernel, grid=(x.shape[0] // block,),
                out_shape=None)(x)
    """)
    assert "R006" in rules_of(bad)


def test_r006_silent_with_pad_or_assert_guard():
    good = lint_snippet("""
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run_padded(x, block: int):
            pad = (-x.shape[0]) % block
            xp = jnp.pad(x, ((0, pad),))
            n = x.shape[0] + pad
            return pl.pallas_call(
                kernel, grid=(n // block,), out_shape=None)(xp)

        def run_asserted(x, block: int):
            assert x.shape[0] % block == 0
            return pl.pallas_call(
                kernel, grid=(x.shape[0] // block,), out_shape=None)(x)
    """)
    assert "R006" not in rules_of(good)


def test_r007_fires_on_dtypeless_creation_in_traced_code():
    bad = lint_snippet("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return x + jnp.arange(4)
    """)
    assert "R007" in rules_of(bad)


def test_r007_silent_with_explicit_dtype_and_outside_trace():
    good = lint_snippet("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return x + jnp.arange(4, dtype=jnp.int32)

        def host_setup():
            return jnp.arange(4)
    """)
    assert "R007" not in rules_of(good)


def test_loss_name_convention_traces_losses_not_factories():
    findings = lint_snippet("""
        import numpy as np

        def recon_loss(params, batch):
            return np.mean(batch)

        def make_loss(lam: float):
            lam = float(lam)
            def loss(params, batch):
                return batch.sum() * lam
            return loss
    """)
    assert [f.symbol for f in findings if f.rule == "R001"] == ["recon_loss"]


# ---------------------------------------------------------------------------
# baseline machinery
# ---------------------------------------------------------------------------

BAD_SRC = """
import jax
import numpy as np

@jax.jit
def f(x):
    return np.log(x)
"""


def test_baseline_suppression_round_trips(tmp_path):
    findings = lint_source(BAD_SRC)
    assert findings
    path = str(tmp_path / "baseline.json")
    write_baseline(path, findings)
    assert apply_baseline(findings, load_baseline(path)) == []
    # a NEW occurrence beyond the frozen count still fails
    doubled = findings + findings
    assert len(apply_baseline(doubled, load_baseline(path))) == len(findings)
    # justifications survive a rewrite
    data = json.load(open(path))
    for e in data["entries"]:
        e["justification"] = "kept on purpose"
    json.dump(data, open(path, "w"))
    write_baseline(path, findings)
    data = json.load(open(path))
    assert all(e["justification"] == "kept on purpose"
               for e in data["entries"])


def test_repo_lints_clean_against_committed_baseline():
    findings = lint_paths(["src/repro"], root=REPO, baseline_path=BASELINE)
    assert findings == [], "\n".join(
        f"{f.file}:{f.line} {f.rule} {f.message}" for f in findings)


def test_lint_cli_exits_zero_and_emits_json():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.lint", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["total"] == 0


# ---------------------------------------------------------------------------
# runtime guards: units
# ---------------------------------------------------------------------------

def test_compile_counter_counts_cold_not_warm():
    @jax.jit
    def poly(x):
        return x * x + 3.0 * x

    x = jnp.full((5, 7), 2.0, jnp.float32)   # shape unique to this test
    with guards.compile_counter() as cold:
        poly(x).block_until_ready()
    assert cold.count >= 1
    with guards.compile_counter(budget=0, label="warm poly"):
        poly(x).block_until_ready()


def test_compile_counter_budget_violation_raises():
    @jax.jit
    def poly(x):
        return x + 1.0

    with pytest.raises(guards.CompileBudgetError):
        with guards.compile_counter(budget=0, label="cold poly"):
            poly(jnp.full((3, 11), 1.0, jnp.float32)).block_until_ready()


def test_no_host_sync_traps_implicit_conversions():
    arr = jnp.ones((4,), jnp.float32)
    for convert in (lambda: np.asarray(arr),
                    lambda: float(arr.sum()),
                    lambda: arr.sum().item(),
                    lambda: arr.tolist()):
        with pytest.raises(guards.HostSyncError):
            with guards.no_host_sync():
                convert()
    # interposition is fully undone outside the block
    assert float(arr.sum()) == 4.0
    assert np.asarray(arr).shape == (4,)


def test_no_host_sync_budgets_explicit_device_get():
    arr = jnp.ones((4,), jnp.float32)
    with guards.no_host_sync(allowed=1) as tally:
        host = jax.device_get(arr)
    assert tally.device_gets == 1 and host.shape == (4,)
    with pytest.raises(guards.HostSyncError):
        with guards.no_host_sync(allowed=0):
            jax.device_get(arr)


def test_audit_dtypes_accepts_engine_family_rejects_others():
    good = {"w": jnp.zeros((2, 2), jnp.float32),
            "step": jnp.zeros((), jnp.int32),
            "mask": jnp.zeros((3,), bool)}
    guards.audit_dtypes(good)
    with pytest.raises(guards.DtypeAuditError):
        guards.audit_dtypes({"w": np.zeros((2,), np.float64)})
    with pytest.raises(guards.DtypeAuditError):
        guards.audit_dtypes({"lr": 0.1})      # python scalar leaf


def test_budgets_file_has_contract_keys():
    budgets = guards.load_budgets()
    assert budgets["train_fit"] == {"warm_compiles": 0, "host_syncs": 1}
    assert budgets["train_lanes_fit"]["host_syncs"] == 1
    assert budgets["serve_stream"]["max_batch_shapes"] == 6
    assert budgets["load_stream"] == {"warm_compiles": 0,
                                      "slo_attainment_min": 0.99}
    assert "float32" in budgets["engine_dtypes"]


# ---------------------------------------------------------------------------
# runtime guards: the engine contracts themselves
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_train_setup():
    from repro.core import autoencoder as ae
    key = jax.random.PRNGKey(0)
    params = ae.init_autoencoder(key, [16, 8, 4, 8, 16])
    x = np.random.RandomState(0).rand(96, 16).astype(np.float32)
    return ae, params, x


def test_train_fit_budget_one_sync_zero_warm_compiles(tiny_train_setup):
    from repro.core import training
    ae, params, x = tiny_train_setup
    budget = guards.load_budgets()["train_fit"]
    kw = dict(max_epochs=3, patience=3, batch_size=32)
    training.train(params, {"x": x}, ae.recon_loss, seed=0, **kw)  # compile
    with guards.compile_counter(budget=budget["warm_compiles"],
                                label="warm train fit"), \
         guards.no_host_sync(allowed=budget["host_syncs"],
                             label="warm train fit") as tally:
        result = training.train(params, {"x": x}, ae.recon_loss, seed=1,
                                **kw)
    assert tally.device_gets == budget["host_syncs"]
    guards.audit_dtypes(result.params, label="train fit params")


def test_train_lanes_fit_budget_one_sync_zero_warm_compiles(
        tiny_train_setup):
    from repro.core import training
    ae, params, x = tiny_train_setup
    budget = guards.load_budgets()["train_lanes_fit"]
    lanes = [training.LaneSpec(params, {"x": x}, seed=s) for s in (0, 1)]
    kw = dict(max_epochs=3, patience=3, batch_size=32)
    training.train_lanes(lanes, ae.masked_recon_loss, **kw)       # compile
    lanes2 = [training.LaneSpec(params, {"x": x}, seed=s) for s in (2, 3)]
    with guards.compile_counter(budget=budget["warm_compiles"],
                                label="warm lanes fit"), \
         guards.no_host_sync(allowed=budget["host_syncs"],
                             label="warm lanes fit") as tally:
        results = training.train_lanes(lanes2, ae.masked_recon_loss, **kw)
    assert tally.device_gets == budget["host_syncs"]
    for r in results:
        guards.audit_dtypes(r.params, label="lane fit params")


@pytest.fixture(scope="module")
def served():
    from repro.core import pipeline
    from repro.experiments.specs import ScenarioSpec
    from repro.experiments.sweeps import build_scenario
    from repro.serve import vfl as sv
    sc = build_scenario(ScenarioSpec(dataset="bcw", n_aligned=120,
                                     n_active_features=5, seed=0))
    result = pipeline.run_apcvfl(sc, seed=0, max_epochs=2)
    engine = sv.VFLServingEngine(sv.export_bundle(result, sc))
    engine.warmup()
    return engine


def test_warmed_serve_bucket_zero_compiles_one_sync_per_dispatch(served):
    budget = guards.load_budgets()["serve_bucket_warm"]
    x = np.random.RandomState(3).rand(
        5, served._mean.shape[0]).astype(np.float32)
    with guards.compile_counter(budget=budget["warm_compiles"],
                                label="warm serve bucket"), \
         guards.no_host_sync(allowed=budget["host_syncs_per_dispatch"],
                             label="warm serve bucket") as tally:
        logits = served.predict_active(x)
    assert logits.shape[0] == 5
    assert tally.device_gets == budget["host_syncs_per_dispatch"]


def test_warmed_serve_stream_stays_within_shape_budget(served):
    budget = guards.load_budgets()["serve_stream"]
    rng = np.random.RandomState(4)
    with guards.compile_counter(budget=0, label="warm serve stream"):
        for n in (1, 2, 3, 5, 8, 13, 21):
            served.predict_active(
                rng.rand(n, served._mean.shape[0]).astype(np.float32))
    shapes = served.compiled_shapes()
    assert shapes["distinct_batch_shapes"] <= budget["max_batch_shapes"]
