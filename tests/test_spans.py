"""Program spans (``repro.core.spans``) in a profile taken on the CPU: which
spans a protocol fit and a meshed lane fit emit, under which parent, and
that the profiler leaves every result as it was."""
import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import autoencoder as ae
from repro.core import pipeline, training
from repro.core.psi import psi
from repro.core.spans import PREFIX
from repro.experiments.specs import ScenarioSpec
from repro.experiments.sweeps import build_scenario
from repro.launch.mesh import make_lane_mesh

SEEDS = [0, 1]
KW = dict(max_epochs=2, batch_size=32)


def traced(tmp_path, fn):
    """``fn()`` under the profiler; returns its result and the program
    spans as (start, end, name), sorted outer first."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = [(e.start_ns, e.end_ns, e.name)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for e in line.events if e.name.startswith(PREFIX)]
    return out, sorted(spans, key=lambda sp: (sp[0], -sp[1]))


def parent(spans, i):
    """The name of the innermost span around span ``i``, or None."""
    s, e = spans[i][:2]
    best = None
    for j, (a, b, n) in enumerate(spans):
        if j != i and a <= s and e <= b and (best is None or a >= best[0]):
            best = (a, n)
    return best and best[1]


@pytest.fixture(scope="module")
def scenarios():
    return [build_scenario(ScenarioSpec(dataset="bcw", n_aligned=120,
                                        n_active_features=5, seed=s))
            for s in SEEDS]


@pytest.fixture(scope="module")
def protocol_fit(scenarios, tmp_path_factory):
    fit = lambda: pipeline.run_apcvfl_replicated(scenarios, seeds=SEEDS,
                                                 **KW)
    untraced = fit()
    results, spans = traced(tmp_path_factory.mktemp("trace"), fit)
    return untraced, results, spans


def test_protocol_fit_emits_each_span_under_its_parent(protocol_fit):
    _, _, spans = protocol_fit
    got = Counter((n, parent(spans, i)) for i, (_, _, n) in
                  enumerate(spans))
    S = len(SEEDS)
    want = {("apcvfl.psi", None): S}
    want.update({(f"apcvfl.{st}", None): 1
                 for st in ("g1", "exchange", "g2", "g3", "probe")})
    # g1 is two shape groups (active and passive widths), g2 and g3 one
    for stage, groups in (("g1", 2), ("g2", 1), ("g3", 1)):
        for name, n in (("prep", groups), ("launch", groups), ("sync", 1),
                        ("unstack", 1)):
            want[(f"apcvfl.lanes.{name}", f"apcvfl.{stage}")] = n
    assert dict(got) == want
    assert len(spans) == S + 5 + 14


def test_profiler_leaves_results_bit_identical(protocol_fit):
    untraced, results, _ = protocol_fit
    for a, b in zip(untraced, results):
        assert a.epochs == b.epochs and a.metrics == b.metrics
        for st in ("g1_active", "g2", "g3"):
            for x, y in zip(jax.tree.leaves(a.params[st]),
                            jax.tree.leaves(b.params[st])):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_meshed_lane_fit_emits_the_shard_span(tmp_path):
    rng = np.random.RandomState(0)
    lanes = [training.LaneSpec(
        ae.init_autoencoder(jax.random.PRNGKey(i), [6, 8, 4]),
        {"x": rng.randn(64, 6).astype(np.float32)}, i) for i in range(2)]
    fit = lambda: training.train_lanes(
        lanes, ae.masked_recon_loss, mesh=make_lane_mesh(lane=1),
        batch_size=16, max_epochs=2)
    fit()                   # compiles outside the trace
    _, spans = traced(tmp_path, fit)
    assert [n for _, _, n in spans] == [
        "apcvfl.lanes.prep", "apcvfl.lanes.shard", "apcvfl.lanes.launch",
        "apcvfl.lanes.sync", "apcvfl.lanes.unstack"]


@pytest.mark.parametrize("n", [10, 2000])
def test_psi_emits_one_span_whatever_the_ids(tmp_path, n):
    ids = np.arange(n, dtype=np.int64)
    (common, idx_a, idx_b), spans = traced(
        tmp_path, lambda: psi(ids, ids[n // 2:]))
    assert [name for _, _, name in spans] == ["apcvfl.psi"]
    np.testing.assert_array_equal(ids[idx_a], ids[n // 2:][idx_b])
    assert len(common) == n - n // 2
